"""window_compiles.tput (count, host): programs compiled or loaded from the compile cache inside the window."""
from chipbench.readers import window_compiles as read  # noqa: F401
