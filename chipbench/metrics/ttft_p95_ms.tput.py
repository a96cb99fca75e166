"""ttft_p95_ms.tput (ms): time to first token in a cell above its knee, recorded and not judged."""
from chipbench.readers import ttft_p95_ms as read  # noqa: F401
