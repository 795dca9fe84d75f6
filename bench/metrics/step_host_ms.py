"""Host time per working step, less its device readbacks; moves itl_p95_ms."""
from bench.spans import step_host_ms as read  # noqa: F401
