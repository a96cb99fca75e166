"""out_tok_s (tokens/s): output tokens that reached the host inside the window, over the window's seconds."""
from chipbench.readers import out_tok_s as read  # noqa: F401
