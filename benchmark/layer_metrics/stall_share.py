"""Seconds lost to ticks the program's stall rule names, over the seconds the ticks span (percent). Source: the batcher's tick ring."""
from benchmark.request_readers import stall_share as read  # noqa: F401
