"""The one traffic generator: it reads a traffic file and drives the
program through its public entries.

A traffic file is data: its ``kind`` names the driver of that kind of work
(``kinds/<kind>.py``, found by name), and every other key is a parameter
of the mix.  A driver does its set-up, then ``warm`` (the engine shapes
the traffic file lists), then opens the window by calling
``window.open()``, keeps starting units while ``window.is_open()`` and
reports each completed one to ``window.unit_done``.  After the window it
returns what the correctness check needs.  Every traffic file also gives
``trace_units``, how many units a traced run records, and
``sample_skip``, the mean number of engine calls skipped between two that
the check keeps.

Every seed the program sees is derived from ``--seed`` (``derive``).
"""

from __future__ import annotations

import zlib
from typing import Dict

import numpy as np


def derive(seed: int, *keys) -> int:
    """A 31-bit seed for the program, derived from ``--seed`` and a key."""
    words = [int(seed) & 0xFFFFFFFFFFFFFFFF] + [
        zlib.crc32(str(k).encode()) for k in keys]
    return int(np.random.SeedSequence(words).generate_state(1)[0] >> 1)


def warm(geom, shapes: Dict) -> None:
    """Run each engine once at each padded shape the traffic may use, so
    the window compiles nothing (a persistent-cache hit after the first
    run of a checkout)."""
    import jax
    import jax.numpy as jnp
    from repro.core import cachesim as cs

    def fresh():
        return cs.init_machine(geom)

    for t in shapes.get("stream", []):
        out = cs.access_stream(fresh(), geom, jnp.full(t, -1, jnp.int32),
                               jnp.zeros(t, jnp.int32), jnp.zeros(t, bool))
        jax.block_until_ready(out)
    for b, t in shapes.get("batched", []):
        out = cs.access_streams_batched(
            fresh(), geom, jnp.full((b, t), -1, jnp.int32),
            jnp.zeros(b, jnp.int32), jnp.zeros(b, bool), jnp.uint32(0))
        jax.block_until_ready(out)
    for g, t in shapes.get("committed", []):
        out = cs.access_streams_committed(
            cs.stack_states([fresh()] * g), geom,
            jnp.full((g, t), -1, jnp.int32), jnp.zeros((g, t), jnp.int32),
            jnp.zeros((g, t), bool))
        jax.block_until_ready(out)
    for g, b, t in shapes.get("batched_multi", []):
        out = cs.access_streams_batched_multi(
            cs.stack_states([fresh()] * g), geom,
            jnp.full((g, b, t), -1, jnp.int32), jnp.zeros((g, b), jnp.int32),
            jnp.zeros((g, b), bool), jnp.zeros(g, jnp.uint32))
        jax.block_until_ready(out)


def dispatches() -> int:
    from repro.core import host_model
    return host_model.probe_dispatch_count()


def kind_of(traffic: Dict):
    """The driver module of a traffic file's kind."""
    from . import harness
    return harness.load_kind(traffic["kind"])
