"""``xla_ms.stream``: device time of every other device op in the traced window, per token or answer that reached the host in it."""

from bench.readers import xla_ms as read  # noqa: F401
