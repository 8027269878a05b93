"""``image_p95_ms``: 95th percentile, over every image due in the window, of due time until both detect tails are on the host."""

from bench.readers import answer_ms as read  # noqa: F401
