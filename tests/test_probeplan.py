"""ProbePlan IR + executor tests (the api_redesign tentpole).

Covers:
  * executor unit semantics — Commit segment fusion (one dispatch,
    state-identical to per-segment traversals), Measure lane trimming,
    Vote majority verdicts vs the hypercall congruence oracle,
    Wait/WarmTimer side effects;
  * plan fusion — `fuse` merges structurally congruent plans into one
    program sharing dispatches and `split_result` restores per-plan
    outputs bit for bit;
  * `execute_many` — G guests' plans as one vectorized program: shapes
    with heterogeneous lane counts, bit-identical per-guest results and
    machine states vs single-guest execution, congruence/shared-host
    guards;
  * plan-vs-legacy parity, property-style: the whole VEV/VCOL/VSCAN
    pipeline (`run_cachex`) must reproduce, field for field, the reports
    recorded from the pre-redesign path on every platform (tier-1:
    skylake_sp; rest `slow`), and the closed-loop fleet must reproduce its
    recorded reports under sequential and lockstep plan execution while
    the lockstep matrix issues >= 2x fewer physical probe dispatches per
    tick than the recorded legacy loop.  The recordings live in
    ``tests/data``; each says how it was made under ``"provenance"``.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cachesim, probeplan, trace
from repro.core.abstraction import ProbeConfig
from repro.core.cachesim import CacheGeometry, MachineGeometry
from repro.core.eviction import VEV, _probe_lanes
from repro.core.host_model import (CotenantWorkload, GuestVM, SimHost,
                                   polluter_gen, probe_dispatch_count)
from repro.core.plancost import SHAPE_CACHE
from repro.core.platforms import get_platform, list_platforms
from repro.core.probeplan import (Commit, Measure, PlanLowering, ProbePlan,
                                  Segment, Vote, Wait, WarmTimer)
from repro.core.runner import run_cachex
from tests.conftest import make_vm

FAST_PLATFORM = "skylake_sp"
DATA = os.path.join(os.path.dirname(__file__), "data")


def _fixture(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def _as_recorded(report, skip):
    """A report's fields as the fixtures hold them: JSON-encoded (dict
    keys become strings, numpy scalars plain numbers)."""
    return {f.name: json.loads(json.dumps(getattr(report, f.name),
                                          default=lambda o: o.item()))
            for f in dataclasses.fields(report) if f.name not in skip}


def _matrix_params():
    return [name if name == FAST_PLATFORM
            else pytest.param(name, marks=pytest.mark.slow)
            for name in list_platforms()]


def _twin_vms(n=2, seed=7, **kw):
    """n identically-booted (host, vm) pairs: same seeds => same hidden
    page tables and machine states, so state evolutions are comparable."""
    return [make_vm(seed=seed, **kw) for _ in range(n)]


def _states_equal(a, b):
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ---------------------------------------------------------------------------
# executor units
# ---------------------------------------------------------------------------

def test_commit_fuses_segments_into_one_dispatch():
    (h1, vm1), (h2, vm2) = _twin_vms()
    pages = vm1.alloc_pages(8)
    vm2.alloc_pages(8)                      # twin allocator stays in sync
    seg_a = np.array([vm1.gva(int(p), 0) for p in pages[:4]])
    seg_b = np.array([vm1.gva(int(p), 64) for p in pages[4:]])
    plan = ProbePlan(ops=(Commit(segments=(Segment(seg_a, 0),
                                           Segment(seg_b, 1))),))
    probeplan.execute(vm1, plan)
    assert vm1.stat_passes == 1             # both segments, ONE dispatch
    # reference: per-segment committed traversals on the twin
    vm2.access(seg_a, vcpu=0)
    vm2.access(seg_b, vcpu=1)
    assert vm2.stat_passes == 2
    # same machine end state (padding no-ops only shift the LRU clock,
    # compare the tag arrays which encode all cache contents)
    _states_equal(h1.state["l2"][0], h2.state["l2"][0])
    _states_equal(h1.state["llc"][0], h2.state["llc"][0])


def test_commit_unfused_hint_keeps_per_segment_dispatches():
    host, vm = make_vm(seed=9)
    pages = vm.alloc_pages(4)
    segs = tuple(Segment(np.array([vm.gva(int(p), 0)]), 0) for p in pages)
    plan = ProbePlan(ops=(Commit(segments=segs),),
                     hints=PlanLowering(fuse_commits=False))
    probeplan.execute(vm, plan)
    assert vm.stat_passes == len(segs)      # legacy one-per-segment route


def test_wait_and_warm_ops_drive_vm_side_effects():
    host, vm = make_vm(seed=11)
    t0 = host.time_ms
    probeplan.execute(vm, ProbePlan(ops=(Wait(ms=5.0), WarmTimer())))
    assert host.time_ms == t0 + 5.0
    assert vm._timer_warm == vm.timer_warm_reads


def test_measure_returns_trimmed_per_lane_latencies():
    host, vm = make_vm(seed=13)
    pages = vm.alloc_pages(6)
    lanes = tuple(np.array([vm.gva(int(p), 0) for p in pages[:n]])
                  for n in (1, 4, 6))
    res = probeplan.execute(vm, ProbePlan(
        ops=(WarmTimer(), Measure(lanes=lanes, vcpus=(0, 0, 0))),))
    assert [len(l) for l in res.last] == [1, 4, 6]
    assert vm.stat_passes == 1


def test_vote_matches_pre_plan_majority_verdicts():
    """The executor's Vote lowering must reach exactly the hypercall
    oracle's verdicts: a test evicts iff it holds at least ``ways`` lines
    congruent with its target (`hypercall_llc_setslice`)."""
    host, vm = make_vm(seed=15)
    pages = vm.alloc_pages(256)
    target = vm.gva(int(pages[0]), 0)
    key = vm.hypercall_llc_setslice(target)
    cong = [vm.gva(int(p), 0) for p in pages[1:]
            if vm.hypercall_llc_setslice(vm.gva(int(p), 0)) == key]
    other = [vm.gva(int(p), 0) for p in pages[1:]
             if vm.hypercall_llc_setslice(vm.gva(int(p), 0)) != key]
    ways = host.geom.llc.n_ways
    tests = [(target, np.array(cong[:ways + 2])),
             (target, np.array(other[:2 * ways]))]
    thr = VEV._threshold("llc")
    ref = np.array([
        sum(vm.hypercall_llc_setslice(int(g)) == key for g in c) >= ways
        for _, c in tests])
    plan = ProbePlan(ops=(Vote(lanes=tuple(_probe_lanes(tests, 1)),
                               vcpus=(0, 0), threshold=thr, votes=3),))
    got = probeplan.execute(vm, plan).last
    np.testing.assert_array_equal(ref, got)
    assert list(got) == [True, False]


# ---------------------------------------------------------------------------
# fusion
# ---------------------------------------------------------------------------

def test_fuse_and_split_roundtrip_shares_dispatches():
    (h1, vm1), (h2, vm2) = _twin_vms(seed=17)
    pages = vm1.alloc_pages(64)
    vm2.alloc_pages(64)
    thr = VEV._threshold("llc")

    def plans_for(vm):
        lanes = [np.array([vm.gva(int(p), 0) for p in pages[a:b]])
                 for a, b in ((0, 20), (20, 44), (44, 64))]
        return [ProbePlan(ops=(Vote(lanes=(l,), vcpus=(0,),
                                    threshold=thr, votes=2),))
                for l in lanes]

    fused, spans = probeplan.fuse(plans_for(vm1))
    assert fused.signature() == ("Vote",)
    split = probeplan.split_result(probeplan.execute(vm1, fused), spans)
    assert vm1.stat_passes == 2             # one dispatch per vote, fused
    singles = [probeplan.execute(vm2, p) for p in plans_for(vm2)]
    assert vm2.stat_passes == 6             # 3 plans x 2 votes, unfused
    for s, r in zip(split, singles):
        np.testing.assert_array_equal(s.last, r.last)


def test_fuse_rejects_structural_mismatch():
    lane = (np.array([1, 2]),)
    vote = ProbePlan(ops=(Vote(lanes=lane, vcpus=(0,), threshold=1),))
    measure = ProbePlan(ops=(Measure(lanes=lane, vcpus=(0,)),))
    with pytest.raises(ValueError):
        probeplan.fuse([vote, measure])
    other = ProbePlan(ops=(Vote(lanes=lane, vcpus=(0,), threshold=1,
                                votes=5),))
    with pytest.raises(ValueError):
        probeplan.fuse([vote, other])


# ---------------------------------------------------------------------------
# execute_many: vmap over guests
# ---------------------------------------------------------------------------

def test_execute_many_matches_single_execution_bitwise():
    """G guests with *different* states and lane counts co-execute as one
    vectorized program; every guest's latencies AND committed machine state
    must equal its standalone execution (the property the fleet's lockstep
    bit-identity rests on)."""
    seeds = (21, 22, 23)
    joint = [make_vm(seed=s) for s in seeds]
    solo = [make_vm(seed=s) for s in seeds]

    def plan_for(vm, n_lanes):
        pages = vm.alloc_pages(16)
        prime = np.array([vm.gva(int(p), 0) for p in pages])
        lanes = tuple(np.array([vm.gva(int(p), 64) for p in pages[:2 + i]])
                      for i in range(n_lanes))
        return ProbePlan(ops=(Commit(segments=(Segment(prime, 0),)),
                              Wait(ms=2.0), WarmTimer(),
                              Measure(lanes=lanes,
                                      vcpus=(0,) * n_lanes)),
                         label="t.monitor")

    lane_counts = (0, 3, 5)                  # heterogeneous (incl. empty)
    jplans = [plan_for(vm, n) for (_, vm), n in zip(joint, lane_counts)]
    splans = [plan_for(vm, n) for (_, vm), n in zip(solo, lane_counts)]
    before = probe_dispatch_count()
    jres = probeplan.execute_many([vm for _, vm in joint], jplans)
    assert probe_dispatch_count() - before == 2   # Commit + Measure, fused
    sres = [probeplan.execute(vm, p) for (_, vm), p in zip(solo, splans)]
    for (jh, jvm), (sh, svm), jr, sr, n in zip(joint, solo, jres, sres,
                                               lane_counts):
        assert len(jr.last) == n
        for a, b in zip(jr.last, sr.last):
            np.testing.assert_array_equal(a, b)
        _states_equal(jh.state["l2"][0], sh.state["l2"][0])
        _states_equal(jh.state["llc"][0], sh.state["llc"][0])
        assert jh.time_ms == sh.time_ms
        # per-guest cost accounting and rng-salt sequencing must match the
        # standalone path exactly (a lane-less guest issues no measure
        # pass and keeps its _probe_seq untouched)
        assert jvm.stat_passes == svm.stat_passes
        assert jvm.stat_accesses == svm.stat_accesses
        assert jvm._probe_seq == svm._probe_seq


def test_execute_many_guards():
    (h1, vm1), (h2, vm2) = _twin_vms(seed=25)
    lane = (np.array([vm1.gva(0, 0)]),)
    vote = ProbePlan(ops=(Vote(lanes=lane, vcpus=(0,), threshold=1),))
    measure = ProbePlan(ops=(Measure(lanes=lane, vcpus=(0,)),))
    with pytest.raises(ValueError):
        probeplan.execute_many([vm1, vm2], [vote, measure])
    with pytest.raises(ValueError):          # one host per guest
        probeplan.execute_many([vm1, vm1], [measure, measure])
    with pytest.raises(ValueError):
        probeplan.execute_many([vm1], [measure, measure])
    salted = ProbePlan(ops=(Measure(lanes=lane, vcpus=(0,), salt=3),))
    with pytest.raises(ValueError):          # rng salts must agree
        probeplan.execute_many([vm1, vm2], [measure, salted])


def _distinct_states(geom, g):
    """g machine states of ``geom`` that differ in every leaf: each guest
    ran its own stream (one stream length, so one compile), then had its
    clock and rng moved by its index."""
    states = []
    for i in range(g):
        blocks = jnp.arange(8, dtype=jnp.int32) * (i + 3)
        st, _ = cachesim.access_stream(
            cachesim.init_machine(geom), geom, blocks,
            jnp.full(8, i % geom.n_cores, jnp.int32), jnp.zeros(8, bool))
        st["clock"] = st["clock"] + i
        st["rng"] = st["rng"] ^ jnp.uint32(i)
        states.append(st)
    return states


def _leaves_identical(got, want):
    """Same tree, and per leaf the same dtype, shape and values."""
    got_leaves, got_def = jax.tree_util.tree_flatten(got)
    want_leaves, want_def = jax.tree_util.tree_flatten(want)
    assert got_def == want_def
    for a, b in zip(got_leaves, want_leaves):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a, b)


# milan_ccx's L3 is non-inclusive, skylake_sp's inclusive
@pytest.mark.parametrize("platform,g", [
    pytest.param(None, 2, id="twins")] + [
    pytest.param(name, g, id=f"{name}-{g}")
    for name in ("milan_ccx", "skylake_sp") for g in (1, 2, 32)])
def test_stack_unstack_states_roundtrip(platform, g):
    """The compiled stack and unstack are exact copies: leaf by leaf, dtype
    included, they equal ``np.stack`` and numpy slicing of the inputs."""
    if platform is None:     # two twin VMs, one clock bumped
        (h1, _), (h2, _) = _twin_vms(seed=27)
        h2.state["clock"] = h2.state["clock"] + 7
        states = [h1.state, h2.state]
    else:
        states = _distinct_states(get_platform(platform).machine(), g)
    host = [jax.tree_util.tree_map(np.asarray, s) for s in states]
    want = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *host)
    stacked = cachesim.stack_states(states)
    _leaves_identical(stacked, want)
    back = cachesim.unstack_states(stacked, g)
    assert len(back) == g
    for i in range(g):
        _leaves_identical(back[i],
                          jax.tree_util.tree_map(lambda x: x[i], want))
        _leaves_identical(back[i], host[i])
        _states_equal(back[i], states[i])   # the inputs were not donated


def test_staging_compiles_once_per_guest_count_and_geometry():
    # a geometry no other test uses, so every entry here is this test's
    geom = MachineGeometry(n_domains=1, cores_per_domain=3,
                           l2=CacheGeometry(n_sets=8, n_ways=3),
                           llc=CacheGeometry(n_sets=16, n_ways=5))
    sizes = lambda: (cachesim._stack._cache_size(),
                     cachesim._unstack._cache_size())

    def roundtrip(g):
        states = _distinct_states(geom, g)
        cachesim.unstack_states(cachesim.stack_states(states), g)

    s0 = sizes()
    roundtrip(3)
    s1 = sizes()
    assert s1 == (s0[0] + 1, s0[1] + 1)
    roundtrip(3)                        # same G and geometry: no entry
    assert sizes() == s1
    roundtrip(4)                        # a new G: exactly one more each
    assert sizes() == (s1[0] + 1, s1[1] + 1)


def test_lockstep_wait_compiles_once_per_shard_and_bucket():
    """A lockstep Wait compiles one committed program per (shard, padded
    length); a length group smaller than its shard is filled with inert
    rows, so it reuses the shard's shape."""
    # a geometry no other test uses, so every entry here is this test's
    geom = MachineGeometry(n_domains=1, cores_per_domain=2,
                           l2=CacheGeometry(n_sets=8, n_ways=2),
                           llc=CacheGeometry(n_sets=32, n_ways=7))

    def guest(seed, rate):
        host = SimHost(geom, n_host_pages=1 << 10, seed=seed)
        host.add_cotenant(CotenantWorkload(
            "noise", 0, rate, polluter_gen(region_pages=64)))
        return GuestVM(host, n_guest_pages=64, vcpu_cores=[0, 1],
                       seed=seed)

    def wait(vms):
        plan = ProbePlan(ops=(Wait(ms=7.0),),
                         hints=PlanLowering(shard_size=2))
        probeplan.execute_many(vms, [plan] * len(vms))

    size = cachesim.access_streams_committed._cache_size
    s0 = size()
    wait([guest(s, 30.0) for s in range(4)])          # 210: (2, 512)
    assert size() == s0 + 1
    assert SHAPE_CACHE.seen("committed", geom, (2, 512))
    wait([guest(s, 30.0) for s in range(10, 14)])     # same shard, bucket
    assert size() == s0 + 1
    # one guest of each shard pads to 1024: a group of one, filled with an
    # inert row to the shard's (2, 1024)
    wait([guest(20, 30.0), guest(21, 100.0), guest(22, 100.0),
          guest(23, 30.0)])
    assert size() == s0 + 2
    assert SHAPE_CACHE.seen("committed", geom, (2, 1024))
    wait([guest(30, 100.0), guest(31, 30.0), guest(32, 30.0),
          guest(33, 100.0)])
    assert size() == s0 + 2


# ---------------------------------------------------------------------------
# plan vs pre-redesign parity (property-style, per platform)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", _matrix_params())
def test_pipeline_plan_vs_legacy_parity(name):
    """VEV + VCOL + VSCAN + CAS/CAP through `run_cachex`: the ProbePlan
    route must reproduce the pre-redesign path's recorded report field for
    field (everything except dispatch/wall cost — fused commits are the
    point), and issue no more dispatches than that path did."""
    plat = get_platform(name)
    cfg = ProbeConfig.for_platform(plat, seed=3)
    a = run_cachex(plat, monitor_intervals=2, config=cfg)
    want = _fixture("pipeline_reports.json")["reports"][name]
    got = _as_recorded(a, ("dispatches", "wall_s"))
    assert got.keys() == want.keys() - {"pre_plan_dispatches"}
    for field, value in got.items():
        assert value == want[field], field
    # fusion never adds dispatches
    assert a.dispatches <= want["pre_plan_dispatches"]


def _cotenant_counts(since=(0, 0)):
    """(``cotenant_dispatches``, ``cotenant_accesses``) less ``since``."""
    return (trace.counter("cotenant_dispatches") - since[0],
            trace.counter("cotenant_accesses") - since[1])


def test_fleet_lockstep_parity_and_dispatch_reduction():
    """The fleet acceptance property: lockstep multi-guest execution
    reproduces every report metric bit for bit vs both the sequential plan
    path and the recorded pre-plan legacy path, while issuing >= 2x fewer
    physical probe dispatches per tick than the legacy per-guest loop did
    (recorded), and G times fewer co-tenant engine calls than the
    sequential plan path."""
    from repro.core.fleet import FleetSim, _run_lockstep
    combos = (("eevdf", "on"), ("cas", "on"), ("cas", "off"))
    kw = dict(n_intervals=6, warmup=2, seed=0)

    recorded = _fixture("fleet_lockstep_reports.json")
    legacy = [recorded["reports"][f"{p}-{c}"] for p, c in combos]
    legacy_loop = recorded["legacy_loop_dispatches"]

    seq_sims = [FleetSim(FAST_PLATFORM, policy=p, cap=c, **kw)
                for p, c in combos]
    c0 = _cotenant_counts()
    seq = [s.run() for s in seq_sims]
    seq_cotenant = _cotenant_counts(c0)

    lock_sims = [FleetSim(FAST_PLATFORM, policy=p, cap=c, **kw)
                 for p, c in combos]
    d0, c0 = probe_dispatch_count(), _cotenant_counts()
    lock = _run_lockstep(lock_sims)
    lock_loop = probe_dispatch_count() - d0
    lock_cotenant = _cotenant_counts(c0)

    skip = ("dispatches", "wall_s", "guests_per_sec")
    for l, s, k in zip(legacy, seq, lock):
        got = _as_recorded(s, skip)
        assert got.keys() == l.keys()
        for f in dataclasses.fields(type(s)):
            if f.name in skip:
                continue
            assert got[f.name] == l[f.name], f.name
            assert getattr(s, f.name) == getattr(k, f.name), f.name
    # the acceptance ratio: physical probe dispatches per tick, whole fleet
    assert legacy_loop >= 2 * lock_loop, (legacy_loop, lock_loop)
    # each lockstep Wait runs the G guests' co-tenant streams in one call:
    # G times fewer co-tenant engine calls over the same accesses
    assert lock_cotenant[1] == seq_cotenant[1] > 0
    assert seq_cotenant[0] >= len(combos) * lock_cotenant[0] > 0, (
        seq_cotenant, lock_cotenant)
