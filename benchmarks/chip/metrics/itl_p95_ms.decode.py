"""95th percentile of the gaps between output tokens
(``harness.readers.itl_p95_ms``)."""

from harness.readers import itl_p95_ms as read  # noqa: F401
