"""The program's own time to the first token, submission to first token, the queue included (ms, p90). Source: the batcher's request ring."""
from benchmark.request_readers import submit_to_first_p90_ms as read  # noqa: F401
