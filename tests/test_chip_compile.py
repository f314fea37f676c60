"""Chip-compile rehearsal: the main-path programs compiled for a described
TPU v5e chip (no chip attached), at the sizes `chip_smoke.py` runs.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU compiler library,
and under several test workers only the worker given this file does.
What these tests show is that the chip's compiler accepts the programs
(Mosaic kernels included) and that the Table 1 batched program fits the
chip's memory; nothing runs, so they say nothing about results or times.
"""

import os

import jax
import jax.numpy as jnp
import pytest

import chip_smoke
from repro.core import cachesim as cs
from repro.core.fleet import fleet_interval_progress
from repro.kernels._compat import lane_block
from repro.kernels.cache_probe.kernel import prime_probe, triad
from repro.kernels.cache_probe.ops import triad_rows
from repro.kernels.cachesim_step.kernel import lru_sets

HBM_LIMIT = 16 * (1 << 30)
TABLE1 = cs.MachineGeometry(l2=cs.SKYLAKE_L2, llc=cs.skylake_llc(20))
ENGINE = chip_smoke.ENGINE


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def spec(one_chip):
    def make(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


@pytest.fixture(scope="module")
def state_spec(spec):
    def make(lead=()):
        state = jax.eval_shape(lambda: cs.init_machine(TABLE1))
        return jax.tree_util.tree_map(
            lambda x: spec(lead + x.shape, x.dtype), state)
    return make


def test_batched_engine_fits_one_chip(spec, state_spec):
    B, T = ENGINE["n_lanes"], ENGINE["lane_len"]
    compiled = cs.access_streams_batched.lower(
        state_spec(), TABLE1, spec((B, T)), spec((B,)),
        spec((B,), jnp.bool_), spec((), jnp.uint32)).compile()
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes)
    # every lane copies the whole Table 1 machine state: a real share of
    # the chip, but it must fit
    assert HBM_LIMIT // 4 < total < HBM_LIMIT


def test_batched_multi_engine_compiles(spec, state_spec):
    G, B, T = 4, 64, ENGINE["lane_len"]
    compiled = cs.access_streams_batched_multi.lower(
        state_spec((G,)), TABLE1, spec((G, B, T)), spec((G, B)),
        spec((G, B), jnp.bool_), spec((G,), jnp.uint32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < HBM_LIMIT


def test_committed_and_stream_engines_compile(spec, state_spec):
    G, T = ENGINE["n_guests"], ENGINE["commit_len"]
    committed = cs.access_streams_committed.lower(
        state_spec((G,)), TABLE1, spec((G, T)), spec((G, T)),
        spec((G, T), jnp.bool_)).compile()
    # the stacked states are donated: the chip updates them in place
    assert committed.memory_analysis().alias_size_in_bytes > 0
    n = ENGINE["warm_len"]
    stream = cs.access_stream.lower(
        state_spec(), TABLE1, spec((n,)), spec((n,)),
        spec((n,), jnp.bool_)).compile()
    assert stream.memory_analysis().alias_size_in_bytes > 0


def test_fleet_interval_progress_compiles(spec):
    B, D = 3, 2
    f32 = jnp.float32
    compiled = fleet_interval_progress.lower(
        spec((B,)), spec((B,), f32), spec((B,)), spec((B,)), spec((B,), f32),
        spec((B,), f32), spec((B,), f32), spec((D,), f32), spec((), f32),
        n_domains=D, ticks=32).compile()
    assert compiled.as_text()


@pytest.mark.parametrize("kernel", ["lru_sets", "prime_probe", "triad"])
def test_kernel_compiles_to_mosaic(kernel, spec):
    rows, T = chip_smoke.KERNEL_ROWS, chip_smoke.KERNEL_T
    if kernel == "lru_sets":
        w = chip_smoke.LRU_WAYS
        fn = lambda t, a, s: lru_sets(t, a, s, block_rows=lane_block(rows))
        args = (spec((rows, w)), spec((rows, w)), spec((rows, T)))
    elif kernel == "prime_probe":
        w = chip_smoke.PRIME_WAYS
        fn = lambda t, a, s, g: prime_probe(t, a, s, g,
                                            block_lanes=lane_block(rows))
        args = (spec((rows, w)), spec((rows, w)), spec((rows, T)),
                spec((rows,)))
    else:
        n = triad_rows(chip_smoke.HBM_PROBE_BYTES)
        fn = triad
        args = (spec((n, 128), jnp.float32), spec((n, 128), jnp.float32),
                spec((1,), jnp.float32))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
