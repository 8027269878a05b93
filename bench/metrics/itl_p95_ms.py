"""``itl_p95_ms``: 95th percentile over every gap between consecutive tokens of every sequence due in the window."""

from bench.readers import token_gap_ms as read  # noqa: F401
