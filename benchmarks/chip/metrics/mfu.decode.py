"""The whole served path's share of the chip's peak (``harness.readers.mfu``)."""

from harness.readers import mfu as read  # noqa: F401
