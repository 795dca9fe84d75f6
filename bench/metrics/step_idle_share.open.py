"""Host share of step(): open-loop cells; moves itl_p95_ms."""
from bench.layer import step_idle_share as read  # noqa: F401
