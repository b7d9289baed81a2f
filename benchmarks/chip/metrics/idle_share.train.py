"""The device's idle share (``harness.readers.idle_share``)."""

from harness.readers import idle_share as read  # noqa: F401
