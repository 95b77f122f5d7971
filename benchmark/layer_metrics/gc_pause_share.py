"""Collector pauses that ended in the window's ticks, over the seconds the ticks span (percent). Source: the batcher's tick ring."""
from benchmark.request_readers import gc_pause_share as read  # noqa: F401
