"""A tenant's monitoring loop through ``CacheXSession.refresh()``: what the
program records of each interval, that recording it changes nothing, and
the Table-1 associativity (a 16-way L2 over an 11-way, two-slice LLC) at
a test size, against the hypercall oracles and the chip benchmark's plain
references.

Covers:
  * spans ``session:refresh`` and ``session:apply``, one each per
    interval;
  * counter ``cotenant_accesses``: the co-tenant accesses each ``Wait``
    issues, ``int(rate_per_ms * window_ms)`` per enabled co-tenant;
  * views, per-set rates and machine state bit-identical with tracing
    on and off;
  * a session on the Table-1-shaped host attaches exactly, and its
    refreshes under a polluter give the reference's rates and views.
"""

import json
import os

import numpy as np
import pytest

from repro.core import CacheXSession, ProbeConfig, probeplan, trace
from repro.core.platforms import NoiseSpec

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "bench", "data", "tiny.json")
# the chip benchmark's skylake_sp_t1 configuration at a test size: every
# way count, the slice count and the inclusion kept, set counts cut
T1_SMALL = {"name": "t1_small", "base_platform": "skylake_sp",
            "l2": {"n_sets": 128, "n_ways": 16},
            "llc": {"n_sets": 128, "n_ways": 11, "n_slices": 2},
            "llc_ways_total": 11, "n_domains": 1, "cores_per_domain": 2,
            "replacement": "lru", "inclusion": "inclusive",
            "provisioning": "shared"}
POLLUTER = dict(domain=0, rate_per_ms=30.0, region_pages=1024)


@pytest.fixture(autouse=True)
def _isolated(monkeypatch):
    """Tracer off and empty around each test; the platforms the tests
    build are registered by name and kept out of later tests."""
    from repro.core import platforms
    monkeypatch.setattr(platforms, "_REGISTRY", dict(platforms._REGISTRY))
    trace.disable()
    trace.reset()
    yield
    trace.disable()
    trace.reset()


def _platform(cfg):
    from benchmarks.chip import harness
    return harness.build_platform(cfg)


def _monitoring(plat, seed=7, guest_pages=2048):
    """An eagerly attached session on a quiet host, then a polluter in
    the guest's own LLC domain."""
    host, vm = plat.make_host_vm(seed=seed, n_guest_pages=guest_pages)
    s = CacheXSession.attach(vm, plat, ProbeConfig.for_platform(plat,
                                                                seed=seed),
                             eager=True)
    host.add_cotenant(NoiseSpec("polluter", **POLLUTER).workload())
    return host, s


def _waits(monkeypatch):
    """Record the ``Wait`` ops of every monitoring plan executed."""
    waits = []
    execute = probeplan.execute

    def counted(vm, plan):
        if plan.label == "vscan.monitor":
            waits.extend(op for op in plan.ops
                         if isinstance(op, probeplan.Wait))
        return execute(vm, plan)

    monkeypatch.setattr(probeplan, "execute", counted)
    return waits


def test_refresh_records_one_refresh_and_one_apply_span_per_interval(
        monkeypatch):
    _, s = _monitoring(_platform(json.load(open(TINY))))
    waits = _waits(monkeypatch)
    trace.enable()
    for _ in range(3):
        s.refresh()
    spans = trace.snapshot()["spans"]
    assert spans["session:refresh"]["count"] == 3
    assert spans["session:apply"]["count"] == 3
    assert spans["plan:vscan.monitor"]["count"] == 3
    assert len(waits) == 3
    parents = {(name, parent) for name, parent, _, _ in
               trace.snapshot()["intervals"]}
    assert ("session:apply", "session:refresh") in parents


def test_cotenant_accesses_count_each_wait_of_the_interval(monkeypatch):
    host, s = _monitoring(_platform(json.load(open(TINY))))
    host.add_cotenant(NoiseSpec("second", domain=1, rate_per_ms=12.5,
                                region_pages=64).workload())
    waits = _waits(monkeypatch)
    c0 = trace.counter("cotenant_accesses")
    d0 = trace.counter("cotenant_dispatches")
    for _ in range(4):
        s.refresh()
    rates = [wl.rate_per_ms for wl in host.cotenants if wl.enabled]
    want = sum(int(r * w.ms) for w in waits for r in rates)
    assert want > 0
    assert trace.counter("cotenant_accesses") - c0 == want
    assert trace.counter("cotenant_dispatches") - d0 == len(waits) == 4


def _intervals(plat, n=5):
    _, s = _monitoring(plat)
    out = []
    for _ in range(n):
        v = s.refresh()
        out.append((v.per_domain, v.per_color, v.mean_rate, v.window_ms,
                    s._vs.history[-1].rate.tolist()))
    st = s.vm.host.state
    state = [np.asarray(a).tolist() for a in (*st["l2"], *st["llc"],
                                                st["clock"], st["rng"])]
    return out, state, s.vm.stat_accesses


def test_monitoring_bit_identical_with_tracing_on():
    plat = _platform(json.load(open(TINY)))
    off = _intervals(plat)
    trace.enable()
    on = _intervals(plat)
    assert trace.snapshot()["spans"]["session:refresh"]["count"] == 5
    assert on == off


def test_table1_associativity_attaches_exactly_and_monitors_as_reference(
        monkeypatch):
    """16-way L2 over an 11-way, two-slice LLC: the session's topology,
    eviction sets, monitored sets and color filters stand against the
    host's page table, and the monitor's per-set rates and published
    views equal the plain references' over the lanes it was handed."""
    from benchmarks.chip import checks
    from benchmarks.chip import reference as ref
    plat = _platform(T1_SMALL)
    assert (plat.l2.n_ways, plat.llc.n_ways, plat.llc.n_slices) == (16, 11, 2)
    _, s = _monitoring(plat)
    faults = checks.abstraction_faults(s, plat)
    assert faults["abstraction_faults"] == 0
    assert faults["abstraction_checked"] > 4
    assert s.topology().detected_associativity == 11
    mon = s.monitored_sets()
    assert mon and {len(m.es.gvas) for m in mon} == {11}

    handed = []
    execute = probeplan.execute

    def kept(vm, plan):
        result = execute(vm, plan)
        if plan.label == "vscan.monitor":
            handed.append((list(plan.meta["order"]),
                           float(plan.meta["window_ms"]),
                           [np.array(x) for x in result.last]))
        return result

    monkeypatch.setattr(probeplan, "execute", kept)
    views, rates, live = [], [], []
    for _ in range(6):
        v = s.refresh()
        views.append((v.per_domain, v.per_color))
        rates.append(s._vs.history[-1].rate.copy())
        live.append(~s._vs.flagged)
    assert len(handed) == 6
    levels = [m.level for m in mon]
    want = [ref.set_rates(lanes, order, levels, w)
            for order, w, lanes in handed]
    assert any(r.any() for r in want)       # the polluter evicted lines
    for got, w in zip(rates, want):
        np.testing.assert_array_equal(got, w)
    want_views = ref.ewma_views(
        want, live, np.array([m.level == "llc" for m in mon]),
        np.array([m.domain for m in mon]), np.array([m.color for m in mon]),
        s.config.ewma_alpha)
    assert views == want_views
