"""Shared vectorized LRU-touch row update for the cache kernels.

Both `cachesim_step` (full per-set simulation) and `cache_probe`
(Prime+Probe verdicts) apply the same predicated access to a block of
independent cache sets; keeping the hit/empty/LRU-victim selection in one
place keeps the kernels bit-identical by construction.

Layout is TPU-native: ways run along sublanes and sets along the 128-wide
lane axis, so a (W, R) tile holds R sets and one access per set is a
(1, R) row.  Way selection uses int32 min-reductions over the way axis
(first index of the minimum, built from an iota) instead of
``argmin``/``argmax``, which Mosaic lowers for float32 only; the choice
of way is identical to ``argmin``/``argmax`` (lowest index on ties).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

INT_MAX = jnp.iinfo(jnp.int32).max


def _first(mask, way):
    """(1, R) index of the first True way per set (W where none)."""
    return jnp.min(jnp.where(mask, way, mask.shape[0]), axis=0,
                   keepdims=True)


def lru_touch(tags, age, blk, clk):
    """One predicated access across a block of sets.

    tags/age: (W, R) int32 (-1 marks an empty way); blk: (1, R) int32
    block per set (-1 = no-op); clk: int32 timestamp written to the
    touched way.  Returns (tags, age, hit) with hit: (1, R) bool (False
    for no-ops).
    """
    way = jax.lax.broadcasted_iota(jnp.int32, tags.shape, 0)
    valid = blk >= 0
    hit_mask = tags == blk
    hit_way = _first(hit_mask, way)
    hit = (hit_way < tags.shape[0]) & valid
    empty = tags == -1
    empty_way = _first(empty, way)
    masked_age = jnp.where(empty, INT_MAX, age)
    lru = _first(masked_age == jnp.min(masked_age, axis=0, keepdims=True),
                 way)
    victim = jnp.where(empty_way < tags.shape[0], empty_way, lru)
    write = (way == jnp.where(hit, hit_way, victim)) & valid
    tags = jnp.where(write, blk, tags)
    age = jnp.where(write, clk, age)
    return tags, age, hit
