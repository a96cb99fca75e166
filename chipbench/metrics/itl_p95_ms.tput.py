"""itl_p95_ms.tput (ms): inter-token gap in a cell above its knee, recorded and not judged."""
from chipbench.readers import itl_p95_ms as read  # noqa: F401
