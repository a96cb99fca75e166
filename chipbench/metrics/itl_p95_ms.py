"""itl_p95_ms (ms): 95th percentile of every gap between consecutive host-visible tokens of a request, both inside the window."""
from chipbench.readers import itl_p95_ms as read  # noqa: F401
