"""ttft_p50_ms (ms): median time to first token over every request due in the window, as ttft_p95_ms counts it."""
from chipbench.readers import ttft_p50_ms as read  # noqa: F401
