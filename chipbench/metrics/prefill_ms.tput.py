"""prefill_ms.tput (ms, model step): mean device time per call of the prefill program (jit_prefill_scatter) in the trace."""
from chipbench.readers import prefill_ms as read  # noqa: F401
