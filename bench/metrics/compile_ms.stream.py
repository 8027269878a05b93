"""``compile_ms.stream``: union of the server's ``jax/trace``, ``jax/lower`` and ``jax/compile`` spans in the traced window, per answer that reached the host in it."""

from bench.progtrace import compile_ms as read  # noqa: F401
