"""``idle_share.decode``: share of the traced window in which no op ran on the device."""

from bench.readers import idle_share as read  # noqa: F401
