"""Pallas TPU kernel: HBM streaming probe (CacheX's Prime phase on TPU).

The TPU adaptation of the paper's eviction-set probe: instead of timing
conflict evictions (no hardware sets on TPU), the monitor times a
STREAM-triad over a buffer sized to blow through VMEM, so wall time is a
direct measure of *effective* HBM bandwidth — the contended, opaque
resource.  The windowed prime/wait/probe structure, EWMA smoothing and
tier logic around this kernel live in `repro.tpuprobe.monitor`.

Tiles are (block, 128) in VMEM — 128-lane aligned; `block` rows of 8-row
sublanes.  Three streams (2 reads + 1 write) make bytes/elem exact:
12 bytes/f32 element.

The Prime+Probe kernel runs its lanes (independent sets) along the
128-wide lane axis, like `cachesim_step`: state tiles are (ways, lanes),
the prime stream (T, lanes), and verdicts an int32 (1, lanes) row that
the wrapper casts to bool.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._compat import CompilerParams
from repro.kernels._lru import lru_touch

TRIAD_BLOCK = 512     # rows per triad grid step


def _triad_kernel(a_ref, b_ref, s_ref, o_ref):
    o_ref[...] = a_ref[...] * s_ref[0] + b_ref[...]


def triad(a, b, scale, *, block: int = TRIAD_BLOCK,
          interpret: bool = False):
    """a, b: (N, 128) f32; scale: (1,) f32 (SMEM) -> (N, 128)."""
    N = a.shape[0]
    block = min(block, N)
    assert N % block == 0
    return pl.pallas_call(
        functools.partial(_triad_kernel),
        grid=(N // block,),
        in_specs=[
            pl.BlockSpec((block, 128), lambda i: (i, 0)),
            pl.BlockSpec((block, 128), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        compiler_params=CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(a, b, scale)


def _prime_probe_kernel(tags_ref, age_ref, stream_ref, target_ref,
                        evicted_ref, *, T: int, clock0: int):
    """Batched multi-set Prime+Probe verdicts over a block of lanes.

    Each lane is one independent LRU cache set.  Install the target (MRU),
    apply the lane's prime stream, then probe: the verdict is whether the
    target was conflict-evicted.  Fully vectorized across the lane block —
    the accelerator-native core of VEV's `evicts_many` group testing, for
    the common single-level case where lanes are pre-resolved to sets.
    """
    tags = tags_ref[...]          # (W, B)
    age = age_ref[...]            # (W, B)
    target = target_ref[...]      # (1, B)

    # prime phase 0: install the target at MRU
    tags, age, _ = lru_touch(tags, age, target, clock0)

    def body(t, carry):
        tags, age = carry
        tags, age, _ = lru_touch(tags, age, stream_ref[pl.ds(t, 1), :],
                                 clock0 + 1 + t)
        return tags, age

    tags, age = jax.lax.fori_loop(0, T, body, (tags, age))
    # probe: evicted iff the target no longer has a resident way
    resident = jnp.max((tags == target).astype(jnp.int32), axis=0,
                       keepdims=True)
    evicted_ref[...] = 1 - resident


def prime_probe(tags, age, streams, targets, *, block_lanes: int = 256,
                clock0: int = 1, interpret: bool = False):
    """tags/age: (B, W) int32; streams: (B, T) -1-padded prime accesses;
    targets: (B,) int32.  Returns evicted verdicts (B,) bool.  On a TPU
    ``block_lanes`` must be a multiple of 128 or all of ``B``."""
    B, W = tags.shape
    T = streams.shape[1]
    block_lanes = min(block_lanes, B)
    assert B % block_lanes == 0

    kernel = functools.partial(_prime_probe_kernel, T=T, clock0=clock0)
    state = pl.BlockSpec((W, block_lanes), lambda b: (0, b))
    row = pl.BlockSpec((1, block_lanes), lambda b: (0, b))
    out = pl.pallas_call(
        kernel,
        grid=(B // block_lanes,),
        in_specs=[state, state,
                  pl.BlockSpec((T, block_lanes), lambda b: (0, b)), row],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((1, B), jnp.int32),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(tags.T, age.T, streams.T, targets[None, :])
    return out[0].astype(jnp.bool_)
