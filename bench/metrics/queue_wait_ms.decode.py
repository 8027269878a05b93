"""``queue_wait_ms.decode``: mean submit to first phase start of the requests served in the traced window (ServerStats queue-delay series)."""

from bench.readers import queue_wait_ms as read  # noqa: F401
