"""Scenario-matrix + batched probe engine tests.

Covers the two tentpole pieces end to end:
  * simulator equivalence — the batched multi-set Prime+Probe engine
    (`cachesim.access_streams_batched`) vs the seed per-access `lax.scan`
    path, exactly, under both `lru` and `random` replacement;
  * the `CachePlatform` registry — VEV/VCOL success criteria parametrized
    across every registered platform (including the CAT-partitioned one,
    whose *effective* associativity shrinks to the allocation), plus the
    `run_cachex` end-to-end driver.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cachesim
from repro.core.cachesim import CacheGeometry, MachineGeometry
from repro.core.color import VCOL, color_accuracy
from repro.core.eviction import VEV
from repro.core.platforms import (CachePlatform, all_platforms, get_platform,
                                  list_platforms)
from repro.core.runner import run_cachex

PLATFORM_NAMES = list_platforms()


# ---------------------------------------------------------------------------
# batched probe engine vs seed scan path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("replacement", ["lru", "random"])
def test_batched_engine_matches_sequential_scan(replacement):
    """Every lane of one batched dispatch must be bit-identical to running
    that lane's stream alone through the seed `access_stream` path from the
    same machine snapshot (with the lane's forked rng under `random`)."""
    geom = MachineGeometry(n_domains=1, cores_per_domain=2,
                           l2=CacheGeometry(64, 4),
                           llc=CacheGeometry(128, 4, 2),
                           replacement=replacement)
    state = cachesim.init_machine(geom)
    rng = np.random.default_rng(11)
    warm = rng.integers(0, 1024, 400).astype(np.int32)
    state, _ = cachesim.access_stream(state, geom, jnp.asarray(warm),
                                      jnp.zeros(400, jnp.int32),
                                      jnp.zeros(400, bool))
    B, T = 6, 48
    blocks = rng.integers(0, 1024, (B, T)).astype(np.int32)
    blocks[blocks % 7 == 0] = -1          # padding holes mid-stream
    cores = rng.integers(0, geom.n_cores, B).astype(np.int32)
    lats_b = np.asarray(cachesim.access_streams_batched(
        state, geom, jnp.asarray(blocks), jnp.asarray(cores),
        jnp.zeros(B, bool)))
    for i in range(B):
        st = jax.tree_util.tree_map(jnp.copy, state)
        st["rng"] = (state["rng"] +
                     jnp.uint32(i) * jnp.uint32(cachesim.RNG_LANE_STRIDE))
        _, lats_s = cachesim.access_stream(
            st, geom, jnp.asarray(blocks[i]),
            jnp.full(T, cores[i], jnp.int32), jnp.zeros(T, bool))
        np.testing.assert_array_equal(lats_b[i], np.asarray(lats_s),
                                      err_msg=f"lane {i} ({replacement})")


@pytest.mark.parametrize("replacement", ["lru", "random"])
def test_batched_evicts_agrees_with_sequential_evicts(replacement):
    """VEV's batched group test must reach the hypercall oracle's verdicts
    on (target, candidates) eviction tests — a test evicts iff it holds at
    least ``ways`` lines congruent with the target — and agree with the
    single-test `evicts` primitive (for `random`, enough votes for the
    majority to be stable)."""
    from tests.conftest import make_vm
    host, vm = make_vm(replacement=replacement, seed=31)
    votes, reps = (5, 4) if replacement == "random" else (1, 1)
    vev = VEV(vm, votes=votes, prime_reps=reps)
    pages = vm.alloc_pages(512)
    target = vm.gva(int(pages[0]), 0)
    key = vm.hypercall_llc_setslice(target)
    cong = [vm.gva(int(p), 0) for p in pages[1:]
            if vm.hypercall_llc_setslice(vm.gva(int(p), 0)) == key]
    other = [vm.gva(int(p), 0) for p in pages[1:]
             if vm.hypercall_llc_setslice(vm.gva(int(p), 0)) != key]
    ways = host.geom.llc.n_ways
    tests = [
        (target, np.array(cong[:ways + 2])),          # clearly evicts
        (target, np.array(cong[:ways - 2] + other[:8])),  # too few congruent
        (target, np.array(other[:2 * ways])),         # disjoint sets
    ]
    oracle = np.array([
        sum(vm.hypercall_llc_setslice(int(g)) == key for g in c) >= ways
        for _, c in tests])
    seq = np.array([vev.evicts(t, c, "llc") for t, c in tests])
    bat = vev.evicts_many(tests, "llc")
    np.testing.assert_array_equal(oracle, bat)
    np.testing.assert_array_equal(seq, bat)
    assert list(bat) == [True, False, False]


# ---------------------------------------------------------------------------
# platform registry
# ---------------------------------------------------------------------------

def test_registry_exposes_scenario_matrix():
    assert len(PLATFORM_NAMES) >= 4
    assert "skylake_sp" in PLATFORM_NAMES
    kinds = {p.provisioning for p in all_platforms()}
    assert {"dedicated", "cat", "slice", "shared"} <= kinds
    cat = get_platform("skylake_cat")
    assert cat.effective_ways < cat.llc_ways_total
    slicep = get_platform("skylake_slicepart")
    assert slicep.llc.n_slices < slicep.llc_slices_total
    assert any(p.noise for p in all_platforms())


@pytest.mark.parametrize("name", PLATFORM_NAMES)
def test_vev_builds_verified_sets_on_every_platform(name):
    """VEV success criteria across the whole provisioning matrix: minimal
    sets of exactly the *effective* associativity, all lines congruent in
    one (set, slice) — checked via the validation hypercall (§6.2)."""
    plat = get_platform(name)
    host, vm = plat.make_host_vm(seed=5)
    vev = VEV(vm, votes=plat.votes, prime_reps=plat.prime_reps)
    ways = plat.effective_ways
    pool = vev.make_pool(0, ways=ways,
                         n_uncontrollable_rows=plat.n_llc_rows_per_offset,
                         n_slices=plat.llc.n_slices)
    sets = vev.build_for_offset(0, pool, ways=ways, level="llc", max_sets=2,
                                seed=6)
    assert len(sets) == 2, f"{name}: built {len(sets)}/2"
    for es in sets:
        assert len(es) == ways, f"{name}: |set|={len(es)} != ways={ways}"
        keys = {vm.hypercall_llc_setslice(int(g)) for g in es.gvas}
        assert len(keys) == 1, f"{name}: set straddles {keys}"


@pytest.mark.parametrize("name", PLATFORM_NAMES)
def test_vcol_virtual_colors_on_every_platform(name):
    """VCOL color filters + parallel filtering across the matrix; quiet
    scenarios must reach the paper's 100% accuracy, noisy ones >= 90%."""
    plat = get_platform(name)
    host, vm = plat.make_host_vm(seed=7)
    vcol = VCOL(vm, vev=VEV(vm, votes=plat.votes,
                            prime_reps=plat.prime_reps))
    cf = vcol.build_color_filters(n_colors=plat.n_l2_colors,
                                  ways=plat.l2.n_ways, seed=8)
    assert cf.n_colors == plat.n_l2_colors, name
    pages = vm.alloc_pages(12 * plat.n_l2_colors)
    colors = vcol.identify_colors_parallel(cf, pages)
    acc = color_accuracy(vm, pages, colors, plat.n_l2_colors)
    if not plat.l2_filter_reliable:
        # small CAT allocations: the simulator's combined LLC/directory
        # entry back-invalidates L2 lines mid-filter (real CAT leaves the
        # snoop-filter directory unpartitioned) — colors stay informative
        # but lose the 100% guarantee
        assert acc >= 0.5, f"{name}: accuracy {acc}"
    elif plat.noise:
        assert acc >= 0.9, f"{name}: accuracy {acc}"
    else:
        assert acc == 1.0, f"{name}: accuracy {acc}"


def test_cat_partitioning_shrinks_detected_associativity():
    """Paper Table 3: under CAT way-partitioning the VM *discovers* its
    allocation — detected ways == allocated ways < hardware ways."""
    cat = get_platform("skylake_cat")
    host, vm = cat.make_host_vm(seed=9)
    vev = VEV(vm)
    pool = vev.make_pool(0, ways=cat.llc_ways_total,
                         n_uncontrollable_rows=cat.n_llc_rows_per_offset,
                         n_slices=cat.llc.n_slices)
    detected = vev.probe_associativity(pool, "llc", seed=10)
    assert detected == cat.effective_ways
    assert detected < cat.llc_ways_total


# ---------------------------------------------------------------------------
# end-to-end driver
# ---------------------------------------------------------------------------

def test_run_cachex_dedicated_baseline():
    r = run_cachex("skylake_sp", seed=1, monitor_intervals=2)
    assert r.vev_success_rate == 1.0
    assert r.detected_ways == 8
    assert r.vcol_accuracy == 1.0
    assert r.vscan_sets > 0
    assert r.vscan_contended_rate > r.vscan_idle_rate
    assert r.cap_allocated > 0
    assert r.dispatches > 0 and r.accesses > 0
    assert r.csv_row().startswith("skylake_sp,dedicated,")
    assert r.csv_header().startswith("platform,provisioning,")


def test_run_cachex_cat_scenario():
    r = run_cachex("skylake_cat", seed=2, monitor_intervals=2)
    assert r.vev_success_rate == 1.0
    assert r.detected_ways == 4          # the CAT allocation, not 8
    assert r.provisioning == "cat"
