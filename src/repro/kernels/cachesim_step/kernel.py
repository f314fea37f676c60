"""Pallas kernel: parallel per-set LRU cache simulation.

The reproduction's compute hot-spot: VSCAN/VEV eviction testing simulates
millions of accesses against thousands of independent cache sets.  Under
LRU, set states are independent, so the paper's "parallel eviction set
construction / monitoring" (Fig 6, Table 6) maps onto a TPU grid over set
rows: each program sequentially applies its rows' access substreams with
fully vectorized tag compare / LRU-victim selection across a block of rows.

Inside the kernel the sets run along the 128-wide lane axis: the state
tile is (ways, rows) and the stream tile (T, rows), so step ``t`` reads
one sublane row of the stream.  The wrapper transposes from and to the
(rows, ways) / (rows, T) interface.  Hits are written as int32 (Mosaic
has no bool memory refs) and cast back to bool outside the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._compat import CompilerParams
from repro.kernels._lru import lru_touch


def _lru_kernel(tags_ref, age_ref, stream_ref, otags_ref, oage_ref,
                hits_ref, *, T: int, clock0: int):
    tags = tags_ref[...]          # (W, R)
    age = age_ref[...]            # (W, R)

    def body(t, carry):
        tags, age = carry
        blk = stream_ref[pl.ds(t, 1), :]            # (1, R)
        tags, age, hit = lru_touch(tags, age, blk, clock0 + t)
        hits_ref[pl.ds(t, 1), :] = hit.astype(jnp.int32)
        return tags, age

    tags, age = jax.lax.fori_loop(0, T, body, (tags, age))
    otags_ref[...] = tags
    oage_ref[...] = age


def lru_sets(tags, age, streams, *, block_rows: int = 256,
             clock0: int = 1, interpret: bool = False):
    """tags/age: (rows, ways); streams: (rows, T) -1-padded.
    Returns (new_tags, new_age, hits (rows, T) bool).  On a TPU
    ``block_rows`` must be a multiple of 128 or all of ``rows``."""
    rows, ways = tags.shape
    T = streams.shape[1]
    block_rows = min(block_rows, rows)
    assert rows % block_rows == 0

    kernel = functools.partial(_lru_kernel, T=T, clock0=clock0)
    state = pl.BlockSpec((ways, block_rows), lambda r: (0, r))
    stream = pl.BlockSpec((T, block_rows), lambda r: (0, r))
    otags, oage, hits = pl.pallas_call(
        kernel,
        grid=(rows // block_rows,),
        in_specs=[state, state, stream],
        out_specs=[state, state, stream],
        out_shape=[
            jax.ShapeDtypeStruct((ways, rows), jnp.int32),
            jax.ShapeDtypeStruct((ways, rows), jnp.int32),
            jax.ShapeDtypeStruct((T, rows), jnp.int32),
        ],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(tags.T, age.T, streams.T)
    return otags.T, oage.T, hits.T.astype(jnp.bool_)
