"""Time-average share of the paged pool's token slots that hold a live request's context (percent). Source: token callbacks."""
from benchmark.readers import pool_fill as read  # noqa: F401
