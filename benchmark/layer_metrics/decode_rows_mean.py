"""Time-average of requests between first and last token in the window (rows). Source: token callbacks."""
from benchmark.readers import decode_rows_mean as read  # noqa: F401
