"""ttft_p95_ms (ms): 95th percentile, nearest rank, of the time from each request's due time to the flush that brought its first token, over every request due in the window; one still waiting at the close counts its wait so far."""
from chipbench.readers import ttft_p95_ms as read  # noqa: F401
