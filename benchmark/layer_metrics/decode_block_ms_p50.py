"""Device time of one decode block (ms, median over the traced window). Source: device trace."""
from benchmark.readers import decode_block_ms_p50 as read  # noqa: F401
