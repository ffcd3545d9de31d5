"""Mean host-clock length of the spans the benchmark records around each
``QueryServer.step`` (coalesce, constant tables, kernel, demux)."""
from bench.metrics.common import tick_ms as read  # noqa: F401
