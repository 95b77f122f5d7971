"""Wait in the batcher's own queue, submission to admission (ms, p90 over the requests admitted in the window). Source: the batcher's request ring."""
from benchmark.request_readers import queue_wait_p90_ms as read  # noqa: F401
