"""Jit'd public wrapper for the flash-attention kernel.

`flash_attention(q, k, v)` takes the model's (B, S, H, D) layout, picks
block sizes from the probed-VMEM budget (CAP-TPU tile selection), and runs
the Pallas kernel — compiled on a TPU, interpreted on the CPU backend
(`repro.kernels._compat.interpret_mode`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels._compat import interpret_mode
from repro.kernels.flash_attention.kernel import flash_attention_bhsd


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 128,
                    block_k: int = 128):
    """q: (B, S, Hq, D); k/v: (B, S, Hkv, D) -> (B, S, Hq, D)."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_attention_bhsd(qt, kt, vt, causal=causal,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret_mode())
    return out.transpose(0, 2, 1, 3)
