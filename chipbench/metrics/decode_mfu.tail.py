"""decode_mfu.tail (%, whole step): model FLOPs per decode step over device time per step times the bf16 peak."""
from chipbench.readers import decode_mfu as read  # noqa: F401
