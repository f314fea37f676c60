"""Jit'd wrapper matching `models.mamba2.ssd_chunked` semantics.

Pre-activates dt (softplus is applied by the caller in mamba2.py — this
wrapper receives raw dt and matches ssd_chunked_ref's contract exactly) and
reshapes the model layout (b, S, h, p) into the kernel's chunked layout.
Adds the D skip term outside the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels._compat import interpret_mode
from repro.kernels.ssd_scan.kernel import ssd_scan_grid


@functools.partial(jax.jit, static_argnames=("chunk", "block_h"))
def ssd_scan(x, dt, A, B, C, D, chunk: int = 128, initial_state=None,
             block_h: int = 8):
    """Same contract as models.mamba2.ssd_chunked_ref (q.v. for shapes)."""
    if initial_state is not None:
        raise NotImplementedError(
            "nonzero initial_state: prefill always starts from zero state; "
            "decode uses the O(1) recurrent step, not this kernel")
    b, S, h, p = x.shape
    n = B.shape[-1]
    nc = max(1, (S + chunk - 1) // chunk)
    L = -(-S // nc)
    assert nc * L == S, "seq must divide into equal chunks"
    if h % block_h != 0:
        block_h = 1

    dtv = jax.nn.softplus(dt.astype(jnp.float32))              # (b,S,h)
    dA = dtv * A.astype(jnp.float32)[None, None, :]

    xk = x.reshape(b, nc, L, h, p).transpose(0, 3, 1, 2, 4)     # (b,h,nc,L,p)
    dtk = dtv.reshape(b, nc, L, h).transpose(0, 3, 1, 2)
    dAk = dA.reshape(b, nc, L, h).transpose(0, 3, 1, 2)
    Bk = B.astype(jnp.float32).reshape(b, nc, L, n)
    Ck = C.astype(jnp.float32).reshape(b, nc, L, n)

    y, st = ssd_scan_grid(xk.astype(jnp.float32), dtk, dAk, Bk, Ck,
                          block_h=block_h, interpret=interpret_mode())
    y = y.transpose(0, 2, 3, 1, 4).reshape(b, S, h, p)
    y = y + x.astype(jnp.float32) * D.astype(jnp.float32)[None, None, :, None]
    return y.astype(x.dtype), st
