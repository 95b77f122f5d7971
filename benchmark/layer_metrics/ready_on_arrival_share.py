"""Ticks whose decode block had finished on the device before the serve thread asked for it, of the ticks that read one back (percent). Source: the batcher's tick ring."""
from benchmark.request_readers import ready_on_arrival_share as read  # noqa: F401
