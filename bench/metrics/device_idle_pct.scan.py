"""Share of the traced window in which no op ran on the device."""
from bench.metrics.common import idle_pct as read  # noqa: F401
