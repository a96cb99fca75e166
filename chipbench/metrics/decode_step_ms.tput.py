"""decode_step_ms.tput (ms, model step): mean device time per call of the decode-step program (jit_step) in the trace."""
from chipbench.readers import decode_step_ms as read  # noqa: F401
