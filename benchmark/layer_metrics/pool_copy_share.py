"""Pool-shaped copies' share of device busy time (percent). Source: device trace."""
from benchmark.readers import pool_copy_share as read  # noqa: F401
