"""The least time a decode step needs on a chip, from shapes alone.

The operations and bytes are the architecture module's
(``decode_flops``, ``decode_bytes``): what the algorithm needs, not what
the compiled program does.
"""
from __future__ import annotations

from typing import Sequence

from chipbench import modelspec


def least_seconds(spec: dict, contexts: Sequence[int], peak: dict) -> float:
    """The larger of the step's FLOPs over the peak FLOP/s and its bytes
    over the peak bytes/s; ``contexts``: the tokens each decoded row
    attends, the new one included."""
    m = modelspec.module(spec)
    return max(m.decode_flops(spec, contexts) / peak["bf16_flops"],
               m.decode_bytes(spec, contexts) / peak["hbm_bytes_s"])
