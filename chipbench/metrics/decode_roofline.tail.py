"""decode_roofline.tail (%, kernels: the whole step until named scopes exist): least time for the bytes and FLOPs a decode step needs, over its device time."""
from chipbench.readers import decode_roofline as read  # noqa: F401
