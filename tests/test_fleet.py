"""Closed-loop fleet simulator tests (paper §4 / §6.3-6.4 / Fig 10).

The expensive end-to-end properties run on one platform (skylake_sp); the
full 6-platform sweep lives in `benchmarks --only fleet`.  The progress
kernel and the summary reducers are covered by fast pure-function tests.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fleet import (FleetReport, FleetSim, default_workloads,
                              fig10_summary, fleet_interval_progress,
                              run_fleet, speedup_summary)
from repro.core.platforms import get_platform
from repro.core.probeplan import ProbePlan


# ---------------------------------------------------------------------------
# vectorized progress / contention-accounting kernel
# ---------------------------------------------------------------------------

def test_progress_kernel_matches_numpy_reference():
    rng = np.random.default_rng(3)
    B, D, T = 5, 3, 24
    domain_idx = rng.integers(0, D, B).astype(np.int32)
    rates = rng.uniform(5, 50, B)
    period = rng.integers(2, 9, B).astype(np.int32)
    duty_on = np.minimum(rng.integers(1, 9, B), period).astype(np.int32)
    sens = rng.uniform(0, 2, B)
    ipc0 = rng.uniform(0.5, 1.5, B)
    slow = rng.uniform(1, 3, B)
    noise = rng.uniform(0, 300, D)
    scale = 0.01

    prog, cont = fleet_interval_progress(
        jnp.asarray(domain_idx), jnp.asarray(rates), jnp.asarray(period),
        jnp.asarray(duty_on), jnp.asarray(sens), jnp.asarray(ipc0),
        jnp.asarray(slow), jnp.asarray(noise), scale,
        n_domains=D, ticks=T)

    ref_prog = np.zeros(B)
    ref_cont = np.zeros((D, T))
    for t in range(T):
        traffic = noise.copy()
        for w in range(B):
            if t % period[w] < duty_on[w]:
                traffic[domain_idx[w]] += rates[w]
        ref_cont[:, t] = traffic * scale
        for w in range(B):
            c = ref_cont[domain_idx[w], t]
            ref_prog[w] += ipc0[w] / ((1 + sens[w] * c) * slow[w])
    np.testing.assert_allclose(np.asarray(prog), ref_prog, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(cont), ref_cont.mean(axis=1),
                               rtol=1e-5)


def test_progress_kernel_contention_hurts_sensitive_more():
    """The Fig 2a shape: same traffic, higher sensitivity => less work."""
    kw = dict(n_domains=1, ticks=16)
    args = (jnp.zeros(2, jnp.int32), jnp.zeros(2), jnp.ones(2, jnp.int32),
            jnp.ones(2, jnp.int32), jnp.array([0.1, 2.0]), jnp.ones(2),
            jnp.ones(2), jnp.array([400.0]), 0.01)
    prog, _ = fleet_interval_progress(*args, **kw)
    assert float(prog[0]) > float(prog[1])


# ---------------------------------------------------------------------------
# closed loop, end to end (one platform)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fleet_pair():
    """(eevdf, cas) reports on skylake_sp, CAP on, shared across tests."""
    return {pol: run_fleet("skylake_sp", policy=pol, cap="on", seed=0)
            for pol in ("eevdf", "cas")}


def test_cas_steers_sensitive_task_to_quiet_domain(fleet_pair):
    """Fig 10, closed-loop: CAS discovers the polluted domain from VSCAN's
    *measured* rates and steers the fleet away; EEVDF affinity pins it."""
    assert fleet_pair["cas"].quiet_residency >= 0.8
    assert fleet_pair["eevdf"].quiet_residency <= 0.2
    assert fleet_pair["cas"].throughput > 1.2 * fleet_pair["eevdf"].throughput


def test_measured_rates_separate_domains(fleet_pair):
    """The decision inputs are real measurements: the polluted domain's
    VSCAN rate must dominate the quiet domain's, and the committed tiers
    must rank the quiet domain better."""
    for r in fleet_pair.values():
        assert r.hot_rate > 2 * r.quiet_rate
        assert r.tiers[0] > r.tiers[1]


def test_cap_protects_working_set():
    """Table 8 analog: with CAP off, the vanilla mixed-color page-cache
    stream evicts the sensitive working set (latency -> DRAM); CAP confines
    the stream to the measured-hottest color and throughput rises."""
    on = run_fleet("skylake_sp", policy="cas", cap="on", seed=0)
    off = run_fleet("skylake_sp", policy="cas", cap="off", seed=0)
    assert on.ws_lat_cycles < 0.5 * off.ws_lat_cycles
    assert on.throughput > off.throughput
    assert on.cap_allocated > 0 and on.reclaims > 0
    assert off.cap_allocated == 0


def test_fleet_report_row_contract(fleet_pair):
    """Headered machine-readable CSV: columns come straight from the
    dataclass fields, so they cannot silently drift."""
    import csv
    import dataclasses
    import io
    header = FleetReport.csv_header().split(",")
    assert header == [f.name for f in dataclasses.fields(FleetReport)]
    row = fleet_pair["cas"].csv_row()
    cells = next(csv.reader(io.StringIO(row)))
    assert len(cells) == len(header)
    assert cells[:3] == ["skylake_sp", "cas", "on"]


def test_fleet_view_widens_topology():
    sim = FleetSim("icelake_sp", n_intervals=1)
    sim_plat = sim.plat
    base = get_platform("icelake_sp")
    assert sim_plat.n_domains >= 2
    assert (sim_plat.cores_per_domain
            >= max(base.cores_per_domain, len(default_workloads())))
    assert sim_plat.llc == base.llc and sim_plat.provisioning == base.provisioning
    # the closed loop's probe points are ProbePlans (what the lockstep
    # driver co-executes), the interval's monitoring plan first
    plan = sim.steps().send(None)
    assert isinstance(plan, ProbePlan) and plan.label == "vscan.monitor"


# ---------------------------------------------------------------------------
# summary reducers (pure functions over synthetic reports)
# ---------------------------------------------------------------------------

def _report(platform, policy, cap, thr, res):
    return FleetReport(
        platform=platform, policy=policy, cap=cap, seed=0, n_intervals=10,
        warmup=4, throughput=thr, per_workload={}, quiet_residency=res,
        hot_rate=5.0, quiet_rate=0.5, tiers={0: 2, 1: 0}, ws_lat_cycles=14.0,
        recolor_events=0, reclaims=0, cap_allocated=0, dispatches=0,
        accesses=0, wall_s=0.0)


def test_fig10_and_speedup_summaries():
    reports = []
    for plat, cas_res in (("a", 1.0), ("b", 0.2)):
        reports += [
            _report(plat, "eevdf", "on", 100.0, 0.0),
            _report(plat, "rusty", "on", 110.0, 0.1),
            _report(plat, "cas", "on", 200.0, cas_res),
            _report(plat, "cas", "off", 160.0, cas_res),
        ]
    f10 = fig10_summary(reports)
    assert f10["n_platforms"] == 2
    assert f10["cas_quiet"] == 1          # only platform "a"
    assert f10["eevdf_pinned"] == 2
    assert f10["separated"] == 1
    assert f10["residency"]["a"]["cas"] == 1.0

    sp = speedup_summary(reports)
    assert sp["a"]["cas_vs_eevdf"] == pytest.approx(1.0)
    assert sp["a"]["cas_vs_rusty"] == pytest.approx(200 / 110 - 1)
    assert sp["a"]["cap_on_vs_off"] == pytest.approx(0.25)
