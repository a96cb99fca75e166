"""idle_share.tail (%, device): 1 - union of device operation intervals over the traced window."""
from chipbench.readers import idle_share as read  # noqa: F401
