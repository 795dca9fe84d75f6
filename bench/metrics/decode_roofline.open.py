"""Decode launch's share of its roofline, open-loop cells; moves itl_p95_ms."""
from bench.layer import decode_roofline as read  # noqa: F401
