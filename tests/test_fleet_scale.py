"""Rack-scale fleet tests: streaming metrics, sharded co-execution, serving.

Covers:
  * the streaming-metrics contract: with ``keep_history=False`` (the
    default) a guest's retained per-series samples stay flat as the run
    gets longer (O(guests), not O(guests x intervals)); the report is
    bit-identical to a ``keep_history=True`` run of the same seed except
    for wall-derived fields; and every report mean equals the running-sum
    mean (a left fold) of the materialized history exactly (plus np.mean
    agreement to float tolerance);
  * sketch quality: the P² quantile estimate lands within a bounded
    relative error of the exact empirical quantile;
  * online residency-phase classification matches the reference
    three-way partition of a materialized residency history;
  * ``choose_shard``: large fleets pick a shard from the platform's
    candidates, small fleets stay unsharded, and the decision is cached;
  * ``ShardedFleet``: donor-cloned guests co-execute under a sharded
    lockstep lowering with per-guest reports bit-identical to the
    unsharded path, and ``guests_per_sec`` is stamped fleet-wide;
  * the serving workload: CAS placement (router tiers fed from published
    ContentionViews) measurably improves ServingGuest p99 latency over
    placement-off on the same seed.
"""

import dataclasses
import functools
import operator

import numpy as np
import pytest

from repro.core.fleet import FleetSim, ShardedFleet
from repro.core.fleetshard import (FleetMetrics, P2Quantile, ResidencyPhases,
                                   choose_shard, clear_shard_cache,
                                   device_groups)
from repro.core.platforms import get_platform

FAST_PLATFORM = "skylake_sp"
# small loop so each guest boots + runs in a couple of seconds
LOOP = dict(n_intervals=4, warmup=1, stream_len=64, ws_pages=4)

WALL_FIELDS = ("wall_s", "guests_per_sec")


def _sim(seed=1, **kw):
    args = dict(policy="cas", cap="on", seed=seed, **LOOP)
    args.update(kw)
    return FleetSim(get_platform(FAST_PLATFORM), **args)


def _report_diff(a, b, ignore=WALL_FIELDS):
    return [f.name for f in dataclasses.fields(a)
            if f.name not in ignore
            and getattr(a, f.name) != getattr(b, f.name)]


# ---------------------------------------------------------------------------
# streaming metrics: memory ceiling + parity with materialized history
# ---------------------------------------------------------------------------

def test_keep_history_off_retained_samples_flat():
    sims = {}
    for n in (4, 8):
        sim = _sim(n_intervals=n)
        sim.run()
        sims[n] = sim.metrics.retained_samples()
    # O(1) per series regardless of run length: the memory ceiling
    assert sims[8] == sims[4]
    grow = {}
    for n in (4, 8):
        sim = _sim(keep_history=True, n_intervals=n)
        sim.run()
        grow[n] = sim.metrics.retained_samples()
    assert grow[8] > grow[4]
    assert grow[4] > sims[4]


def test_keep_history_report_parity():
    # the flag only changes what is retained, never what is reported
    off = _sim(seed=7).run()
    on = _sim(seed=7, keep_history=True).run()
    assert _report_diff(off, on) == []


def test_streaming_means_match_history_exactly():
    sim = _sim(seed=5, keep_history=True)
    rep = sim.run()
    m = sim.metrics
    for name, field in (("ws_lat", rep.ws_lat_cycles),
                        ("hot_rate", rep.hot_rate),
                        ("quiet_rate", rep.quiet_rate)):
        hist = m.history(name)
        assert len(hist) == m.count(name) > 0
        # bit-identical to the running-sum mean the report is built from:
        # a plain left fold (Python 3.12's built-in sum is compensated)
        assert field == functools.reduce(operator.add, hist, 0.0) / len(hist)
        # and within float tolerance of numpy's pairwise mean
        assert np.isclose(field, np.mean(hist), rtol=0, atol=1e-12)


def test_p2_quantile_bounded_error():
    rng = np.random.default_rng(0)
    xs = rng.lognormal(mean=3.0, sigma=0.6, size=5000)
    for q in (0.50, 0.99):
        sk = P2Quantile(q)
        for x in xs:
            sk.add(float(x))
        exact = float(np.quantile(xs, q))
        assert abs(sk.value() - exact) / exact < 0.05


def test_fleet_metrics_window_ring():
    m = FleetMetrics(keep_history=False, window=4)
    for i in range(10):
        m.add("x", float(i))
    assert m.window_values("x") == [6.0, 7.0, 8.0, 9.0]
    assert m.last("x") == 9.0
    assert m.mean("x") == sum(range(10)) / 10


def test_residency_phases_match_reference_partition():
    # reference: materialize the (interval, in_quiet) history and slice it
    # into pre/during/post around [start, end]; the online classifier must
    # produce the same three means without the history
    warmup, start, stop, n_intervals = 2, 5, 12, 20
    rng = np.random.default_rng(3)
    hist = [(k, float(rng.integers(0, 2))) for k in range(n_intervals)]
    for defended_at in (None, 9):
        ph = ResidencyPhases(warmup=warmup, start=start, stop=stop,
                             n_intervals=n_intervals, defend=True)
        for k, v in hist:
            ph.add(k, v, defended=defended_at is not None and k >= defended_at,
                   defended_at=defended_at if defended_at is not None
                   and k >= defended_at else -1)
        ph.finish(defended_at is not None,
                  defended_at if defended_at is not None else -1)
        end = defended_at if defended_at is not None else min(stop,
                                                              n_intervals)
        pre = [v for k, v in hist if warmup <= k < start]
        dur = [v for k, v in hist if start <= k <= end]
        post = [v for k, v in hist if k > end]
        want = tuple(sum(xs) / len(xs) if xs else 0.0
                     for xs in (pre, dur, post))
        assert ph.means() == want


# ---------------------------------------------------------------------------
# choose_shard + device groups
# ---------------------------------------------------------------------------

def test_choose_shard_large_fleet_shards_small_stays_whole():
    plat = get_platform(FAST_PLATFORM)
    clear_shard_cache()
    big = choose_shard(plat, n_guests=256)
    assert big.shard_size in plat.scale.shard_candidates
    assert big.n_shards == -(-256 // big.shard_size)
    assert big.lowering.shard_size == big.shard_size
    small = choose_shard(plat, n_guests=8)
    assert small.shard_size is None
    assert small.n_shards == 1
    again = choose_shard(plat, n_guests=256)
    assert again.cached and again.shard_size == big.shard_size


def test_device_groups_cover_all_guests():
    for n, shard in ((256, 16), (8, None), (5, 2)):
        groups = device_groups(n, shard)
        covered = sorted(i for _, sl in groups for i in range(n)[sl])
        assert covered == list(range(n))


# ---------------------------------------------------------------------------
# ShardedFleet co-execution
# ---------------------------------------------------------------------------

def test_sharded_fleet_reports_match_unsharded():
    # 8 guests is the smallest fleet where the tuner keeps lockstep on
    # for this loop sizing (at 4 it prefers per-guest sequential runs,
    # which would make this comparison vacuous)
    fleets, runs = {}, {}
    for shard in (4, None):                   # None = auto (choose_shard)
        fleets[shard] = ShardedFleet(FAST_PLATFORM, 8, seed=0,
                                     shard_size=shard, **LOOP)
        runs[shard] = fleets[shard].run()
    res = runs[4]
    assert res.n_guests == len(res.reports) == 8
    assert res.shard_size == 4 and res.n_shards == 2
    # non-vacuity: both runs actually co-executed under lockstep, and
    # the auto choice stayed unsharded so this is sharded-vs-whole
    assert fleets[4].sims[0].lowering.shard_size == 4
    assert fleets[4].sims[0].lowering.lockstep
    assert runs[None].shard_size is None and runs[None].n_shards == 1
    assert fleets[None].sims[0].lowering.lockstep
    assert res.guests_per_sec > 0
    assert all(r.guests_per_sec == res.guests_per_sec for r in res.reports)
    # shard_size is dispatch-shape only: per-guest reports bit-identical
    for a, b in zip(runs[4].reports, runs[None].reports):
        assert _report_diff(a, b) == []


# ---------------------------------------------------------------------------
# serving workload: placement moves p99
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", [
    FAST_PLATFORM, pytest.param("milan_ccx", marks=pytest.mark.slow)])
def test_serving_placement_improves_p99(name):
    plat = get_platform(name)
    kw = dict(policy="cas", cap="on", seed=3, serving=True,
              n_intervals=6, warmup=2, stream_len=64, ws_pages=4)
    on = FleetSim(plat, serving_placement=True, **kw).run()
    off = FleetSim(plat, serving_placement=False, **kw).run()
    assert on.serve_requests == off.serve_requests > 0
    assert on.serve_p99_ms > 0 and off.serve_p99_ms > 0
    # blind least-loaded routing keeps sending work into the polluted
    # domain; tier-fed routing avoids it — p99 must drop measurably
    assert on.serve_p99_ms < 0.8 * off.serve_p99_ms
    assert on.serve_p50_ms < off.serve_p50_ms
