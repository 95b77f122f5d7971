"""Device time of one prefill program run (ms, median over the traced window). Source: device trace."""
from benchmark.readers import prefill_p50_ms as read  # noqa: F401
