"""``dispatch_ms.stream``: host time issuing the served phases (phase span start to its ``issued`` stamp), summed over phases in the traced window, per answer that reached the host in it."""

from bench.progtrace import dispatch_ms as read  # noqa: F401
