"""Time decoding rows sat behind a prefill: admit + prefill_sync of ticks with a decode block, over the seconds the ticks span (percent). Source: the batcher's tick ring."""
from benchmark.tick_readers import prefill_stall_share as read  # noqa: F401
