"""Where the Pallas kernels run: compiled on a TPU, interpreted elsewhere.

Every kernel wrapper takes ``interpret=None`` and resolves it here, so a
kernel reached on the chip is never interpreted by default and the CPU
backend (tests, ``JAX_PLATFORMS=cpu``) always interprets.  A caller that
needs one mode regardless of the platform — an equivalence test, or a
compile-only test for a described chip — passes the flag itself.
"""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    return not on_tpu() if interpret is None else interpret
