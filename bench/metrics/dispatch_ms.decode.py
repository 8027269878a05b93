"""``dispatch_ms.decode``: host time issuing the served phases (phase span start to its ``issued`` stamp), summed over phases in the traced window, per token that reached the host in it."""

from bench.progtrace import dispatch_ms as read  # noqa: F401
