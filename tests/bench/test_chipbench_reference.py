"""The benchmark's plain numpy cache model against the program's four JAX
engines, bit for bit, at a small geometry: inclusive and non-inclusive
hierarchies, LRU and random replacement, per-lane rng forks and salts."""

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import checks
from benchmarks.chip import reference as ref
from repro.core import cachesim as cs

VARIANTS = [(inc, repl) for inc in ("inclusive", "non_inclusive")
            for repl in ("lru", "random")]


def _geom(inclusion, replacement):
    return cs.MachineGeometry(
        n_domains=2, cores_per_domain=2,
        l2=cs.CacheGeometry(n_sets=16, n_ways=4),
        llc=cs.CacheGeometry(n_sets=8, n_ways=4, n_slices=2),
        replacement=replacement, inclusion=inclusion)


def _traffic(rng, shape, n_cores):
    """Set-congruent blocks (so sets fill, evict and back-invalidate),
    with padding holes and co-tenant accesses."""
    blocks = (rng.integers(0, 8, shape[:-1] + (1,))
              + 8 * rng.integers(0, 24, shape)).astype(np.int32)
    blocks[rng.random(shape) < 0.05] = -1
    cores = rng.integers(0, n_cores, shape).astype(np.int32)
    cotenant = rng.random(shape) < 0.2
    return blocks, cores, cotenant


def _warm_state(geom, rng):
    """A machine state that has already seen traffic (non-trivial ages,
    clock and rng)."""
    blocks, cores, ct = _traffic(rng, (300,), geom.n_cores)
    state, _ = cs.access_stream(cs.init_machine(geom), geom,
                                jnp.asarray(blocks), jnp.asarray(cores),
                                jnp.asarray(ct))
    return state


def _same(got, want):
    assert checks._mismatch(got, want) == 0


@pytest.mark.parametrize("inclusion,replacement", VARIANTS)
def test_stream_and_committed_match(inclusion, replacement):
    geom = _geom(inclusion, replacement)
    m = checks.machine_of(geom)
    rng = np.random.default_rng(1)
    state = _warm_state(geom, rng)
    before = checks.to_ref_state(state)
    blocks, cores, ct = _traffic(rng, (200,), geom.n_cores)
    new, lats = cs.access_stream(state, geom, jnp.asarray(blocks),
                                 jnp.asarray(cores), jnp.asarray(ct))
    want_state, want_lats = ref.stream(m, before, blocks, cores, ct)
    _same(checks._program_outputs("access_stream", (new, lats)),
          checks._state_list(want_state) + [want_lats])

    states = cs.stack_states([_warm_state(geom, rng) for _ in range(3)])
    before = checks.to_ref_state(states)
    blocks, cores, ct = _traffic(rng, (3, 150), geom.n_cores)
    new, lats = cs.access_streams_committed(
        states, geom, jnp.asarray(blocks), jnp.asarray(cores),
        jnp.asarray(ct))
    want_state, want_lats = ref.committed(m, before, blocks, cores, ct)
    _same(checks._program_outputs("access_streams_committed", (new, lats)),
          checks._state_list(want_state) + [want_lats])


@pytest.mark.parametrize("inclusion,replacement", VARIANTS)
def test_batched_lanes_match(inclusion, replacement):
    geom = _geom(inclusion, replacement)
    m = checks.machine_of(geom)
    rng = np.random.default_rng(2)
    state = _warm_state(geom, rng)
    blocks, _, _ = _traffic(rng, (6, 40), geom.n_cores)
    cores = rng.integers(0, geom.n_cores, 6).astype(np.int32)
    ct = rng.random(6) < 0.3
    for salt in (0, 7):
        lats = cs.access_streams_batched(
            state, geom, jnp.asarray(blocks), jnp.asarray(cores),
            jnp.asarray(ct), jnp.uint32(salt))
        want = ref.batched(m, checks.to_ref_state(state), blocks, cores, ct,
                           salt)
        _same([lats], [want])

    states = cs.stack_states([_warm_state(geom, rng) for _ in range(2)])
    blocks, _, _ = _traffic(rng, (2, 5, 30), geom.n_cores)
    cores = rng.integers(0, geom.n_cores, (2, 5)).astype(np.int32)
    ct = rng.random((2, 5)) < 0.3
    salts = np.array([3, 11], np.uint32)
    lats = cs.access_streams_batched_multi(
        states, geom, jnp.asarray(blocks), jnp.asarray(cores),
        jnp.asarray(ct), jnp.asarray(salts))
    want = ref.batched_multi(m, checks.to_ref_state(states), blocks, cores,
                             ct, salts)
    _same([lats], [want])


def test_slice_hash_is_balanced_and_matches_the_engine():
    blocks = np.arange(4096, dtype=np.int32)
    want = np.asarray(cs.slice_hash(jnp.asarray(blocks), 4, 0x9E3779B9))
    got = ref.slice_of(blocks, 4, 0x9E3779B9)
    assert np.array_equal(got, want)
    assert np.bincount(got).min() > 900


def test_sixteen_bit_control_differs_at_real_block_numbers():
    """The control (the reference in 16-bit integers) must come out wrong
    on the addresses the platforms use: host pages above 2**9 put block
    numbers past 16 bits, and clocks pass 2**15."""
    geom = _geom("inclusive", "lru")
    m = checks.machine_of(geom)
    rng = np.random.default_rng(3)
    blocks, cores, ct = _traffic(rng, (400,), geom.n_cores)
    blocks = np.where(blocks >= 0, blocks + (1 << 18), -1).astype(np.int32)
    state = checks.to_ref_state(cs.init_machine(geom))
    _, want = ref.stream(m, state, blocks, cores, ct)
    _, low = ref.stream(m, state, blocks, cores, ct, np.int16)
    assert checks._mismatch([low], [want]) > 0


# -- the monitor's rates and the placement rules ------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_tier_rule_matches_the_programs_tier_tracker(seed):
    from repro.core.cas import TierTracker
    rng = np.random.default_rng(seed)
    views = [{d: float(rng.choice([0.2, 2.0, 9.0])) for d in (0, 1)}
             for _ in range(40)]
    tt = TierTracker(keys=[0, 1], thresholds=[1.0, 4.0])
    got = []
    for v in views:
        tt.update(v)
        got.append(dict(tt.tier))
    assert ref.tiers(views, [1.0, 4.0]) == got


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_cap_rule_matches_the_programs_allocator(seed):
    from repro.core.cap import CapAllocator
    rng = np.random.default_rng(seed)
    lists = {c: list(range(100 * c, 100 * c + int(rng.integers(0, 12))))
             for c in range(4)}
    cap = CapAllocator(lists, use_contention=True)
    views, got = [], []
    for _ in range(30):
        view = {c: float(rng.choice([0.1, 1.0, 5.0])) for c in range(4)
                if rng.random() < 0.9}
        views.append(view)
        cap.step_interval(view)
        pages = [cap.allocate() for _ in range(10)]
        got.append([cap.page_color[p] for p in pages if p is not None])
        cap.reclaim_all()
    want = ref.cap_colors(views, {c: len(v) for c, v in lists.items()}, 10)
    assert want == got


def test_cas_rule_fills_the_best_tier_first():
    vcpu_domain = {0: 0, 1: 0, 2: 0, 3: 1, 4: 1, 5: 1}
    tier = {0: 2, 1: 0}
    assert not ref.cas_misplaced([3, 4, 5], vcpu_domain, tier)
    assert ref.cas_misplaced([0, 4, 5], vcpu_domain, tier)
    assert ref.cas_misplaced([3, 3, 5], vcpu_domain, tier)
    assert not ref.cas_misplaced([0, 4, 5], vcpu_domain, {})


def test_set_rates_count_lines_above_the_level_threshold():
    lanes = [np.array([14, 200, 50, 14]), np.array([14, 50])]
    rates = ref.set_rates(lanes, [1, 0], ["l2", "llc"], 2.0)
    assert rates.tolist() == [25.0, 12.5]
