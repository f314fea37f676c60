"""The in-program tracer (`repro.core.trace`) and what the program records
through it.

Covers:
  * the off path: a shared null context, no clock read, no profiler
    annotation, nothing recorded;
  * on: ``cachex:`` annotations, nesting, parents, self time, the capped
    interval list, and `spanned` holding no span across a yield;
  * the counters: ``probe_dispatches`` deltas as before (plan dispatch
    counts; co-tenant traffic left out), ``cotenant_dispatches`` one per
    monitor ``Wait`` and guest shard of a fleet interval,
    ``cotenant_lockstep_streams`` one per guest's stream in it,
    ``staging_dispatches`` one per
    stack or unstack program of the multi-guest paths, a measured tune
    leaving every counter as it was;
  * tracing changes no result: a small fleet's reports and an attach's
    views are bit-identical with tracing on and off.
"""

import dataclasses
import json
import os
import types

import numpy as np
import pytest

from repro.core import CacheXSession, ProbeConfig, probeplan, trace
from repro.core.fleet import ShardedFleet
from repro.core.host_model import probe_dispatch_count, shard_slices

from conftest import make_vm

TINY = os.path.join(os.path.dirname(__file__), "bench", "data", "tiny.json")
FLEET = dict(policy="cas", cap="on", thresholds=(1.0, 4.0), stream_len=16,
             ws_pages=2, warmup=0, n_intervals=2)
FLEET_SEED = 1605   # a boot seed whose 2-color test host builds
WALL_FIELDS = ("wall_s", "guests_per_sec")


@pytest.fixture(autouse=True)
def _tracer_restored():
    trace.disable()
    trace.reset()
    yield
    trace.disable()
    trace.reset()


@pytest.fixture
def tiny(monkeypatch):
    """The benchmark's test-size configuration, registered by name (the
    fleet's clones look their donor's platform up) and kept out of later
    tests."""
    from benchmarks.chip import harness
    from repro.core import platforms
    monkeypatch.setattr(platforms, "_REGISTRY", dict(platforms._REGISTRY))
    with open(TINY) as f:
        return harness.build_platform(json.load(f))


class _Clock:
    def __init__(self):
        self.now = 0.0

    def perf_counter(self):
        return self.now


def _fake_annotations(monkeypatch):
    opened = []

    class Annotation:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(trace.jax.profiler, "TraceAnnotation", Annotation)
    return opened


# -- the tracer ---------------------------------------------------------------

def test_off_path_records_nothing_and_opens_no_annotation(monkeypatch):
    def refused(*a, **kw):
        raise AssertionError("the disabled path touched the profiler "
                             "or the clock")

    monkeypatch.setattr(trace.jax.profiler, "TraceAnnotation", refused)
    monkeypatch.setattr(trace, "time",
                        types.SimpleNamespace(perf_counter=refused))
    assert not trace._on
    first = trace.span("a")
    assert trace.span("op:", "Wait") is first   # one shared null context
    with trace.span("outer"):
        with trace.span("inner"):
            pass
    snap = trace.snapshot()
    assert snap["spans"] == {} and snap["intervals"] == []
    assert snap["dropped"] == 0


def test_nesting_parents_and_self_time(monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(trace, "time", clock)
    opened = _fake_annotations(monkeypatch)
    trace.enable()
    with trace.span("plan:", "x"):
        clock.now += 1.0
        with trace.span("op:", "Wait"):
            clock.now += 2.0
            with trace.span("cotenant"):
                clock.now += 4.0
        with trace.span("op:", "Wait"):
            clock.now += 8.0
        clock.now += 16.0
    snap = trace.snapshot()
    assert opened == ["cachex:plan:x", "cachex:op:Wait", "cachex:cotenant",
                      "cachex:op:Wait"]
    assert snap["spans"]["plan:x"] == {"count": 1, "total_s": 31.0,
                                       "self_s": 17.0}
    assert snap["spans"]["op:Wait"] == {"count": 2, "total_s": 14.0,
                                        "self_s": 10.0}
    assert snap["spans"]["cotenant"] == {"count": 1, "total_s": 4.0,
                                         "self_s": 4.0}
    assert snap["intervals"] == [("cotenant", "op:Wait", 3.0, 7.0),
                                 ("op:Wait", "plan:x", 1.0, 7.0),
                                 ("op:Wait", "plan:x", 7.0, 15.0),
                                 ("plan:x", None, 0.0, 31.0)]
    trace.disable()
    with trace.span("later"):
        pass
    assert "later" not in trace.snapshot()["spans"]
    trace.reset()
    assert trace.snapshot()["spans"] == {}


def test_interval_list_is_capped_and_drops_are_counted(monkeypatch):
    monkeypatch.setattr(trace, "MAX_INTERVALS", 3)
    trace.enable()
    for _ in range(5):
        with trace.span("s"):
            pass
    snap = trace.snapshot()
    assert len(snap["intervals"]) == 3 and snap["dropped"] == 2
    assert snap["spans"]["s"]["count"] == 5


def test_spanned_holds_no_span_across_a_yield():
    trace.enable()
    depth = []

    def gen():
        with trace.span("inner"):
            pass
        got = yield "a"
        depth.append(len(trace._stack))
        return got * 2

    g = trace.spanned(gen(), "decide")
    assert next(g) == "a"
    assert trace._stack == []            # nothing open at the yield
    with pytest.raises(StopIteration) as stop:
        g.send(21)
    assert stop.value.value == 42
    assert depth == [1]                  # only "decide" was open inside
    snap = trace.snapshot()
    assert snap["spans"]["decide"]["count"] == 2
    assert snap["intervals"][0][:2] == ("inner", "decide")


def test_counters_are_always_on_and_restorable():
    before = trace.snapshot()["counters"]
    trace.count("probe_dispatches")
    trace.count("device_syncs", 3)
    assert trace.counter("probe_dispatches") == \
        before.get("probe_dispatches", 0) + 1
    assert trace.counter("device_syncs") == before.get("device_syncs", 0) + 3
    trace.reset()                         # spans only: counters are totals
    assert trace.counter("device_syncs") == before.get("device_syncs", 0) + 3
    trace.restore_counters(before)
    assert trace.snapshot()["counters"] == before


# -- the program's counters ---------------------------------------------------

@pytest.mark.parametrize("on", [False, True])
def test_probe_dispatch_deltas_unchanged(on):
    from repro.core.host_model import CotenantWorkload, polluter_gen
    from repro.core.probeplan import (Commit, Measure, ProbePlan, Segment,
                                      Vote, Wait, WarmTimer)
    host, vm = make_vm(n_domains=2, cores_per_domain=2, seed=3)
    host.add_cotenant(CotenantWorkload("noise", 0, 50.0, polluter_gen()))
    lines = np.array([vm.gva(p, 0) for p in range(24)])
    plan = ProbePlan(ops=(
        Commit(segments=(Segment(lines[:8], 0), Segment(lines[8:16], 1))),
        Wait(ms=2.0), WarmTimer(),
        Measure(lanes=(lines[:8], lines[8:]), vcpus=(0, 1)),
        Vote(lanes=(lines[:8],), vcpus=(0,), threshold=100, votes=3)))
    if on:
        trace.enable()
    d0, c0 = probe_dispatch_count(), trace.counter("cotenant_dispatches")
    s0 = trace.counter("device_syncs")
    probeplan.execute(vm, plan)
    assert probe_dispatch_count() - d0 == plan.n_dispatches == 5
    # the Wait's co-tenant stream is counted apart from the probes
    assert trace.counter("cotenant_dispatches") - c0 == 1
    # every engine call here reads its latencies back: 5 probes + 1 stream
    assert trace.counter("device_syncs") - s0 == 6
    if on:
        spans = trace.snapshot()["spans"]
        assert spans["plan:"]["count"] == 1
        assert {k: v["count"] for k, v in spans.items()
                if k.startswith("op:")} == {
            "op:Commit": 1, "op:Wait": 1, "op:WarmTimer": 1,
            "op:Measure": 1, "op:Vote": 1}
        assert spans["device:dispatch"]["count"] == 6
        assert spans["device:sync"]["count"] == 6
        assert spans["cotenant"]["count"] == 1
        assert spans["stage:gen"]["count"] == 1


def _staged(fn, *args, **kw):
    """``staging_dispatches`` added by ``fn(*args, **kw)``."""
    s0 = trace.counter("staging_dispatches")
    fn(*args, **kw)
    return trace.counter("staging_dispatches") - s0


def test_staging_dispatches_per_multi_guest_call():
    from repro.core.host_model import (commit_segments_multi,
                                       timed_access_batch_multi)
    from repro.core.probeplan import Commit, ProbePlan, Segment
    vms = [make_vm(seed=s)[1] for s in (41, 42, 43, 44, 45)]
    lines = [np.array([vm.gva(p, 0) for p in range(8)]) for vm in vms]
    # a Commit with work: one stack, one unstack
    assert _staged(commit_segments_multi, vms[:2],
                   [[(l, 0)] for l in lines[:2]]) == 2
    # none: nothing is staged
    assert _staged(commit_segments_multi, vms[:2], [[], []]) == 0
    # a Measure stacks once and reads latencies back, unstacking nothing
    assert _staged(timed_access_batch_multi, vms[:3],
                   [[l] for l in lines[:3]], [[0]] * 3) == 1
    # a sharded Commit in execute_many: two per shard, [2, 2, 1] here
    plans = [ProbePlan(ops=(Commit(segments=(Segment(l, 0),)),),
                       hints=probeplan.PlanLowering(shard_size=2))
             for l in lines]
    assert _staged(probeplan.execute_many, vms, plans) == 2 * 3


def test_measured_tune_leaves_every_counter_unchanged():
    from repro.core import get_platform
    from repro.core.plancost import tune_lowering
    plat = get_platform("skylake_sp")
    before = trace.snapshot()["counters"]
    tune_lowering(plat, None, n_guests=2, measure=True, force=True)
    assert trace.snapshot()["counters"] == before


def _fleet(plat, n=8):
    return ShardedFleet(plat, n, seed=FLEET_SEED, **FLEET)


def test_cotenant_dispatches_one_per_monitor_wait(tiny, monkeypatch):
    """One co-tenant engine call per monitor ``Wait`` and guest shard: a
    lockstep round runs every guest's stream of the shard in one call
    (the fleet's streams pad to one length), and
    ``cotenant_lockstep_streams`` counts each guest's stream."""
    fl = _fleet(tiny)
    waits, calls = [], []
    execute_many = probeplan.execute_many

    def counted(vms, plans):
        monitor = [p.signature().count("Wait") for p in plans
                   if p.label == "vscan.monitor"]
        if monitor:
            waits.extend(monitor)
            shard = plans[0].effective_lowering().shard_size
            calls.append(monitor[0] * len(shard_slices(len(vms), shard)))
        return execute_many(vms, plans)

    monkeypatch.setattr(probeplan, "execute_many", counted)
    c0 = trace.counter("cotenant_dispatches")
    s0 = trace.counter("cotenant_lockstep_streams")
    fl.run()
    assert len(waits) == 8 * FLEET["n_intervals"]
    assert set(waits) == {1}
    assert trace.counter("cotenant_dispatches") - c0 == sum(calls)
    assert trace.counter("cotenant_lockstep_streams") - s0 == sum(waits)


# -- tracing changes no result ------------------------------------------------

def test_fleet_reports_bit_identical_with_tracing_on(tiny):
    off = _fleet(tiny).run()
    trace.enable()
    on = _fleet(tiny).run()
    spans = trace.snapshot()["spans"]
    assert spans["fleet:decide"]["count"] > 0
    assert spans["plan:vscan.monitor"]["count"] > 0
    assert spans["op:Wait"]["count"] > 0
    for a, b in zip(off.reports, on.reports):
        diff = [f.name for f in dataclasses.fields(a)
                if f.name not in WALL_FIELDS
                and getattr(a, f.name) != getattr(b, f.name)]
        assert diff == []
    assert len(off.reports) == len(on.reports) == 8


def _attach_views(plat):
    _, vm = plat.make_host_vm(seed=7, n_guest_pages=2048)
    s = CacheXSession.attach(vm, plat, ProbeConfig.for_platform(plat,
                                                                seed=7))
    colors = s.colors()
    topo = s.topology()
    mon = s.monitored_sets()
    view = s.refresh()
    return {
        "topology": dataclasses.asdict(topo),
        "offsets": np.asarray(colors.offsets).tolist(),
        "filters": [np.asarray(f.gvas).tolist()
                    for f in colors.filters.filters],
        "llc_sets": [np.asarray(e.gvas).tolist() for e in s.llc_sets()],
        "monitored": [(np.asarray(m.es.gvas).tolist(), m.color, m.domain,
                       m.vcpu, m.level) for m in mon],
        "per_domain": view.per_domain, "per_color": view.per_color,
        "accesses": vm.stat_accesses, "passes": vm.stat_passes,
    }


def test_attach_views_bit_identical_with_tracing_on(tiny):
    off = _attach_views(tiny)
    trace.enable()
    on = _attach_views(tiny)
    spans = trace.snapshot()["spans"]
    for name in ("session:attach", "session:colors", "session:topology",
                 "session:monitored_sets", "stage:pad", "device:sync"):
        assert spans[name]["count"] > 0, name
    assert on == off
