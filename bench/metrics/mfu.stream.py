"""``mfu.stream``: model FLOPs of the real requests served in the traced window over the chip's bf16 peak times the union of the intervals in which a group was in flight (first phase start to results ready)."""

from bench.readers import mfu as read  # noqa: F401
