"""Jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to the platform (``repro.kernels.backend``):
compiled on a TPU, interpreted on the CPU.  The kernels are written
against TPU tiling constraints: 128-lane blocks, MXU-aligned matmul
dims, VMEM scratch accumulators.
"""
from repro.kernels.autotune import autotune_paged_decode
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mlstm_chunk import mlstm_chunkwise
from repro.kernels.paged_attention import (paged_decode_attention,
                                           paged_decode_step)
from repro.kernels.rglru_scan import rglru_scan

__all__ = ["flash_attention", "decode_attention", "paged_decode_attention",
           "paged_decode_step", "rglru_scan", "mlstm_chunkwise",
           "autotune_paged_decode"]
