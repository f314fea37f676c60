"""Jit'd wrapper for the per-set LRU simulation kernel."""

from __future__ import annotations

import functools

import jax

from repro.kernels._compat import interpret_mode, lane_block
from repro.kernels.cachesim_step.kernel import lru_sets


@functools.partial(jax.jit, static_argnames=("clock0",))
def simulate_rows(tags, age, streams, clock0: int = 1):
    return lru_sets(tags, age, streams, block_rows=lane_block(tags.shape[0]),
                    clock0=clock0, interpret=interpret_mode())
