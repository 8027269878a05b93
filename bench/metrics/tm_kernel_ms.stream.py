"""``tm_kernel_ms.stream``: device time of the Mosaic (Pallas) kernel events in the traced window, per token or answer that reached the host in it."""

from bench.readers import tm_kernel_ms as read  # noqa: F401
