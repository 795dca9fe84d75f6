"""Prompt tokens served from shared blocks; moves ttft_p70_ms."""
from bench.layer import prefix_hit_share as read  # noqa: F401
