"""Host time of a tick that ran a decode block: wall time less the device-wait phases (ms, median). Source: the batcher's tick ring."""
from benchmark.tick_readers import tick_host_ms_p50 as read  # noqa: F401
