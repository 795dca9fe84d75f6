"""Mean queue wait of the requests admitted while tracing; moves ttft_p70_ms."""
from bench.spans import queue_wait_ms as read  # noqa: F401
