"""Pallas-TPU helpers shared by the kernel wrappers."""

from __future__ import annotations

import jax
from jax.experimental.pallas import tpu as pltpu

CompilerParams = pltpu.CompilerParams


def interpret_mode() -> bool:
    """Whether Pallas kernels run in the interpreter: True on the CPU
    backend (tests), False on a TPU (compiled).  Any other backend
    raises rather than silently interpreting."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"no Pallas lowering for backend {backend!r}: "
                       "kernels compile on 'tpu' and are interpreted on "
                       "'cpu'")


def lane_block(n: int, cap: int = 256) -> int:
    """Block size along a kernel's lane (last) axis: the largest multiple
    of 128 up to ``cap`` that divides ``n``, else all of ``n`` (the two
    block widths Mosaic accepts on the lane axis)."""
    for b in range(cap - cap % 128, 0, -128):
        if n % b == 0:
            return b
    return n
