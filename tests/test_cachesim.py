"""Unit + property tests for the cache-hierarchy simulator itself."""

import jax.numpy as jnp
import numpy as np
import pytest
from tests._hypothesis_compat import given, settings, st

from repro.core import cachesim
from repro.core.cachesim import (LAT_DRAM, LAT_L2, LAT_LLC, CacheGeometry,
                                 MachineGeometry, slice_hash)
from tests.conftest import make_vm


def test_latency_classes(small_vm):
    host, vm = small_vm
    pages = vm.alloc_pages(4)
    a = vm.gva(int(pages[0]), 0)
    vm.warm_timer()
    assert int(vm.timed_access([a])[0]) == LAT_DRAM   # cold
    vm.warm_timer()
    assert int(vm.timed_access([a])[0]) == LAT_L2     # private-cache hit


def test_l2_eviction_leaves_llc_copy(small_vm):
    host, vm = small_vm
    pages = vm.alloc_pages(1024)
    a = vm.gva(int(pages[0]), 0)
    vm.access([a])
    # fill target's L2 set with 8+ same-L2-set lines (same offset+L2 color)
    tcolor = vm.hypercall_l2_color(int(pages[0])) % 4
    cong = [vm.gva(int(p), 0) for p in pages[1:]
            if vm.hypercall_l2_color(int(p)) % 4 == tcolor][:8]
    # avoid LLC-congruent subsets larger than ways: use only 8 (L2 ways)
    vm.access(np.array(cong))
    vm.warm_timer()
    lat = int(vm.timed_access([a])[0])
    assert lat in (LAT_LLC, LAT_DRAM)
    assert lat > cachesim.L2_MISS_THRESHOLD


def test_llc_eviction_and_back_invalidation(small_vm):
    host, vm = small_vm
    pages = vm.alloc_pages(1024)
    a = vm.gva(int(pages[0]), 0)
    vm.access([a])
    key = vm.hypercall_llc_setslice(a)
    cong = [vm.gva(int(p), 0) for p in pages[1:]
            if vm.hypercall_llc_setslice(vm.gva(int(p), 0)) == key]
    assert len(cong) >= 8
    vm.access(np.array(cong[:8]))  # 8 = LLC ways -> target evicted
    vm.warm_timer()
    # back-invalidation: the line must be gone from the private L2 as well
    assert int(vm.timed_access([a])[0]) == LAT_DRAM


def test_llc_partial_prime_keeps_target(small_vm):
    host, vm = small_vm
    pages = vm.alloc_pages(1024)
    a = vm.gva(int(pages[0]), 0)
    vm.access([a])
    key = vm.hypercall_llc_setslice(a)
    cong = [vm.gva(int(p), 0) for p in pages[1:]
            if vm.hypercall_llc_setslice(vm.gva(int(p), 0)) == key][:7]
    vm.access(np.array(cong))      # ways-1 lines: target must survive
    vm.warm_timer()
    assert int(vm.timed_access([a])[0]) <= LAT_LLC


def test_slice_hash_balance():
    blocks = jnp.arange(1 << 16)
    for n in (2, 4, 20):
        s = np.asarray(slice_hash(blocks, n))
        counts = np.bincount(s, minlength=n)
        assert counts.min() > 0.9 * counts.mean()
        assert counts.max() < 1.1 * counts.mean()


def test_slice_hash_hidden_from_page_offset():
    # lines within one page can land in different slices (uncontrollable)
    blocks = jnp.arange(64) + (1234 << 6)
    s = np.asarray(slice_hash(blocks, 4))
    assert len(np.unique(s)) > 1


def test_domain_isolation():
    host, vm = make_vm(n_domains=2, cores_per_domain=2)
    pages = vm.alloc_pages(2)
    a = vm.gva(int(pages[0]), 0)
    vm.access([a], vcpu=0)          # domain 0
    vm.warm_timer()
    # a core in domain 1 must not see it in its own LLC
    assert int(vm.timed_access([a], vcpu=2)[0]) == LAT_DRAM
    vm.warm_timer()
    # but a sibling core in domain 0 is served by the shared LLC
    b = vm.gva(int(pages[1]), 0)
    vm.access([b], vcpu=0)
    vm.warm_timer()
    assert int(vm.timed_access([b], vcpu=1)[0]) == LAT_LLC


def test_cotenant_evicts_and_back_invalidates(small_vm):
    host, vm = small_vm
    pages = vm.alloc_pages(8)
    a = vm.gva(int(pages[0]), 0)
    vm.access([a])
    blk = vm._hpa_block(np.array([a]))[0]
    # co-tenant hammers the same LLC set with congruent blocks
    base = (1 << 18) * 64
    cand = base + np.arange(1 << 14)
    same_set = cand[cand % host.geom.llc.n_sets == blk % host.geom.llc.n_sets]
    k = min(64, len(same_set))
    host._run_stream(same_set[:k].astype(np.int32),
                     np.zeros(k, np.int32), np.ones(k, bool))
    vm.warm_timer()
    assert int(vm.timed_access([a])[0]) == LAT_DRAM


@settings(max_examples=20, deadline=None)
@given(ways=st.integers(2, 8), n_access=st.integers(1, 40), seed=st.integers(0, 99))
def test_property_lru_set_never_overflows(ways, n_access, seed):
    """Occupancy of any set never exceeds its ways; a just-accessed line is
    always resident (MRU safety)."""
    geom = MachineGeometry(n_domains=1, cores_per_domain=1,
                           l2=CacheGeometry(n_sets=16, n_ways=4),
                           llc=CacheGeometry(n_sets=32, n_ways=ways, n_slices=1))
    state = cachesim.init_machine(geom)
    rng = np.random.default_rng(seed)
    blocks = rng.integers(0, 256, size=n_access).astype(np.int32)
    state, lats = cachesim.access_stream(
        state, geom, jnp.asarray(blocks), jnp.zeros(n_access, jnp.int32),
        jnp.zeros(n_access, bool))
    occ = cachesim.llc_occupancy(state)
    assert occ.max() <= ways
    assert cachesim.resident_level(state, int(blocks[-1]), 0, geom) in (2, 3)


def test_random_replacement_policy_runs():
    host, vm = make_vm(replacement="random")
    pages = vm.alloc_pages(64)
    gvas = np.array([vm.gva(int(p), 0) for p in pages])
    vm.access(gvas)
    vm.warm_timer()
    lats = vm.timed_access(gvas[:8])
    assert set(np.unique(lats)) <= {LAT_L2, LAT_LLC, LAT_DRAM,
                                    LAT_L2 + vm.timer_noise_lat,
                                    LAT_LLC + vm.timer_noise_lat,
                                    LAT_DRAM + vm.timer_noise_lat}


def test_cotenant_traffic_routes_to_its_domain():
    """CotenantWorkload.domain must steer LLC traffic into that domain
    (regression: all co-tenants once landed in domain 0)."""
    from repro.core.host_model import CotenantWorkload, polluter_gen
    host, vm = make_vm(n_domains=2, cores_per_domain=2)
    host.add_cotenant(CotenantWorkload(
        "d1", 1, 100.0, polluter_gen(region_pages=512)))
    vm.wait_ms(5.0)
    occ0 = cachesim.llc_occupancy(host.state, domain=0).sum()
    occ1 = cachesim.llc_occupancy(host.state, domain=1).sum()
    assert occ1 > 0
    assert occ0 == 0


def test_donating_engines_consume_the_callers_state():
    """`access_stream` and `access_streams_committed` donate the machine
    state.  The CPU backend deletes donated buffers just as the TPU does,
    so a caller that kept a state it passed in fails here too."""
    geom = MachineGeometry(n_domains=1, cores_per_domain=1,
                           l2=CacheGeometry(n_sets=16, n_ways=4),
                           llc=CacheGeometry(n_sets=32, n_ways=4, n_slices=1))
    blocks = jnp.arange(8, dtype=jnp.int32)
    state = cachesim.init_machine(geom)
    new, _ = cachesim.access_stream(state, geom, blocks,
                                    jnp.zeros(8, jnp.int32),
                                    jnp.zeros(8, bool))
    assert state["l2"][0].is_deleted() and not new["l2"][0].is_deleted()
    stacked = cachesim.stack_states([new, cachesim.init_machine(geom)])
    out, _ = cachesim.access_streams_committed(
        stacked, geom, jnp.stack([blocks] * 2), jnp.zeros((2, 8), jnp.int32),
        jnp.zeros((2, 8), bool))
    assert stacked["llc"][0].is_deleted()
    # the stacked copy left the per-guest state intact
    assert not new["l2"][0].is_deleted()
    assert int(out["clock"][0]) == int(out["clock"][1]) + 8

