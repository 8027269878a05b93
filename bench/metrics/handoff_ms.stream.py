"""``handoff_ms.stream``: per served group, its in-flight time (first phase start to its requests' response) outside the union of its phase spans, summed over the traced window, per answer that reached the host in it."""

from bench.progtrace import handoff_ms as read  # noqa: F401
