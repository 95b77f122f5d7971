"""Admission to first token per 1,000 padded prompt tokens dispatched (ms, median). Source: the batcher's request ring."""
from benchmark.request_readers import admit_to_first_ms_per_ktok_p50 as read  # noqa: F401
