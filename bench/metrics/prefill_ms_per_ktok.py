"""Prefill device time per 1,000 prompt tokens; moves ttft_p70_ms."""
from bench.layer import prefill_ms_per_ktok as read  # noqa: F401
