"""Serve-thread time in phases that do not wait for the device, over the seconds the ticks span (percent). Source: the batcher's tick ring."""
from benchmark.tick_readers import host_gap_share as read  # noqa: F401
