"""setup_s (s): process start to the window's open: runtime, weights, every program compiled or loaded, warm-up."""
from chipbench.readers import setup_s as read  # noqa: F401
