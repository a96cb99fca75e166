"""queue_wait_p95_ms (ms, scheduler): 95th percentile of due time to the start of the step that issued the request's prefill."""
from chipbench.readers import queue_wait_p95_ms as read  # noqa: F401
