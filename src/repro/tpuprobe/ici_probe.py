"""ICI link probing: timed collectives per mesh axis (VTOP-TPU).

The paper's VTOP infers hidden vCPU topology from cache-line transfer
latencies; on a pod the hidden quantity is per-axis/per-link ICI health
(degraded optics, a flaky chip's serdes, cross-slice DCN contention).  We
time (a) a small `psum` per mesh axis and (b) neighbor `ppermute` rings,
via shard_map — the latency matrix recovers which axis/hop is degraded.

On CPU the timing is meaningless, so `probe_axes` accepts an injected
`link_model(axis, hop) -> slowdown`; the inference logic (ranking axes,
flagging degraded hops) is identical on real hardware.
"""

from __future__ import annotations

import functools
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.launch.mesh import ICI_BW_PER_LINK


def _axis_psum_probe(mesh: Mesh, axis: str, n_floats: int = 1 << 16):
    """A jitted one-axis psum over a small buffer."""

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=P(axis), out_specs=P(axis))
    def probe(x):
        return jax.lax.psum(x, axis) / mesh.shape[axis]

    size = mesh.shape[axis]
    x = jnp.ones((size * n_floats,), jnp.float32)
    return jax.jit(probe), x


def _ring_permute_probe(mesh: Mesh, axis: str, n_floats: int = 1 << 16):
    size = mesh.shape[axis]
    perm = [(i, (i + 1) % size) for i in range(size)]

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=P(axis), out_specs=P(axis))
    def probe(x):
        return jax.lax.ppermute(x, axis, perm)

    x = jnp.ones((size * n_floats,), jnp.float32)
    return jax.jit(probe), x


def probe_axes(mesh: Mesh,
               link_model: Optional[Callable[[str, int], float]] = None,
               n_floats: int = 1 << 14) -> Dict[str, Dict]:
    """Returns per-axis {psum_s, ring_s, slowdown} estimates.

    With `link_model` (CPU validation) the timing is synthesized on top of
    the functional collectives, which still run (proving the shard_map
    programs are valid for the mesh).
    """
    out: Dict[str, Dict] = {}
    for axis in mesh.axis_names:
        psum_fn, px = _axis_psum_probe(mesh, axis, n_floats)
        ring_fn, rx = _ring_permute_probe(mesh, axis, n_floats)
        # functional execution (validity proof; negligible data)
        psum_fn(px).block_until_ready()
        ring_fn(rx).block_until_ready()
        nbytes = px.size * 4
        nominal = nbytes / ICI_BW_PER_LINK
        if link_model is None:
            t0 = time.perf_counter()
            psum_fn(px).block_until_ready()
            t_psum = time.perf_counter() - t0
            t0 = time.perf_counter()
            ring_fn(rx).block_until_ready()
            t_ring = time.perf_counter() - t0
        else:
            worst = max(link_model(axis, h)
                        for h in range(mesh.shape[axis]))
            t_psum = nominal * 2 * worst     # ring all-reduce ~ 2 passes
            t_ring = nominal * worst
        out[axis] = {
            "psum_s": t_psum,
            "ring_s": t_ring,
            "slowdown": max(1.0, t_ring / max(nominal, 1e-12)),
            "size": mesh.shape[axis],
        }
    return out


def rank_axes_by_health(axis_stats: Dict[str, Dict]) -> list:
    """Least-contended axis first (consumed by the rebalancer when choosing
    where to place bandwidth-hungry collectives, e.g. grad compression only
    on the slowest axis)."""
    return sorted(axis_stats, key=lambda a: axis_stats[a]["slowdown"])


def degraded_hops(mesh: Mesh, axis: str,
                  link_model: Callable[[str, int], float],
                  threshold: float = 1.3) -> list:
    """Per-hop ring probes isolate WHICH link is sick (VTOP's pairwise
    latency matrix, one axis at a time)."""
    return [h for h in range(mesh.shape[axis])
            if link_model(axis, h) > threshold]
