"""Device time per decode launch, open-loop cells; moves itl_p95_ms."""
from bench.layer import decode_step_ms as read  # noqa: F401
