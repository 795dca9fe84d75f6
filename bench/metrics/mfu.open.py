"""Whole step's share of the chips' peak, open-loop cells; moves itl_p95_ms."""
from bench.layer import mfu as read  # noqa: F401
