"""How late the load generator ran (ms, p99 over requests due in the window). Source: host clock."""
from benchmark.readers import gen_late_p99_ms as read  # noqa: F401
