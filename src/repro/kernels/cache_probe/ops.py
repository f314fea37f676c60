"""Jit'd wrappers: HBM streaming probe + batched Prime+Probe verdicts."""

from __future__ import annotations

import functools
import time
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels._compat import interpret_mode, lane_block
from repro.kernels.cache_probe.kernel import TRIAD_BLOCK, prime_probe, triad


@jax.jit
def probe_triad(a, b, scale):
    return triad(a, b, scale, interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("clock0",))
def probe_verdicts(tags, age, streams, targets, clock0: int = 1):
    """Batched multi-set Prime+Probe eviction verdicts (one fused call).

    The accelerator-native fast path for B simultaneous single-set eviction
    tests; swept against `ref.prime_probe_ref` in tests/test_kernels.py and
    against the full machine simulator's batched engine (which adds slices,
    the L2 layer and back-invalidation) in tests/test_platforms.py.
    """
    return prime_probe(tags, age, streams, targets,
                       block_lanes=lane_block(tags.shape[0]),
                       clock0=clock0, interpret=interpret_mode())


def triad_rows(n_bytes: int) -> int:
    """Rows of the (rows, 128) f32 triad operands for an ``n_bytes``
    working set over three streams: a whole number of triad blocks, at
    least one."""
    n_elems = n_bytes // 4 // 3
    return max(1, n_elems // 128 // TRIAD_BLOCK) * TRIAD_BLOCK


def measure_hbm_bandwidth(n_bytes: int = 256 * (1 << 20),
                          reps: int = 3) -> Tuple[float, float]:
    """Run the triad over an `n_bytes` working set; returns
    (effective_bytes_per_s, elapsed_s).  On real TPU this is the paper's
    eviction-rate analogue; on CPU it validates the code path (the
    simulated-contention clock in tpuprobe.monitor feeds the policy)."""
    rows = triad_rows(n_bytes)
    a = jnp.ones((rows, 128), jnp.float32)
    b = jnp.ones((rows, 128), jnp.float32)
    s = jnp.ones((1,), jnp.float32)
    probe_triad(a, b, s).block_until_ready()      # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = probe_triad(a, b, s)
    out.block_until_ready()
    dt = (time.perf_counter() - t0) / reps
    bytes_moved = rows * 128 * 4 * 3
    return bytes_moved / dt, dt
