"""Pallas attention custom-calls' share of device busy time (percent). Source: device trace."""
from benchmark.readers import attn_kernel_share as read  # noqa: F401
