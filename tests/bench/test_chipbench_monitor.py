"""The ``skylake_sp_t1.monitor`` cell: its files found by name, its
configuration built at the published associativity, a short run of the
``monitor`` kind at a test size on the CPU held correct while the control
is not, each fault the cell can have planted and caught, and the cell's
metric readers reading nothing without their record."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import checks, harness
from benchmarks.chip import run as bench

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SPEC = harness.load_spec(ROOT)
CELL = "skylake_sp_t1.monitor"
TINY = json.load(open(os.path.join(ROOT, "tests", "bench", "data",
                                   "tiny.json")))
TRAFFIC = {"kind": "monitor", "guest_pages": 2048,
           "polluter": {"domain": 0, "rate_per_ms": 30.0,
                        "region_pages": 256},
           "warmup_intervals": 2, "sample_skip": 2, "trace_units": 1,
           "warm_shapes": {}}
SECONDS = 2.0
METRICS = [m["name"] for m in SPEC["per_layer"]
           if CELL in m.get("workloads", [])]


@pytest.fixture(autouse=True)
def _registry_restored(monkeypatch):
    from repro.core import platforms
    monkeypatch.setattr(platforms, "_REGISTRY", dict(platforms._REGISTRY))


def _run(seed=11, control=False):
    res = bench.run_cell(TINY, TRAFFIC, seed, SECONDS, False, "", None, jax,
                         control=control)
    return res, bench.judge(res)


def test_cell_files_are_found_by_name():
    cell = harness.find_cell(SPEC, CELL)
    assert cell["chips"] == 1
    cfg = harness.load_json(harness.config_path(cell["config"]))
    traffic = harness.load_json(harness.traffic_path(cell["traffic"]))
    kind = harness.load_kind(traffic["kind"])
    assert kind.NUMBERS == ("window_error", "engine_mismatch",
                            "abstraction_faults", "rate_gap", "view_gap")
    assert set(kind.NUMBERS) <= set(checks.LIMITS)
    assert cfg["name"] == "skylake_sp_t1"
    assert len(METRICS) == 5
    for name in METRICS + ["fleet_gi_per_s", "setup_s"]:
        assert callable(harness.load_metric(name))
    e2e = [m["name"] for m in harness.cell_metrics(SPEC, CELL, False)]
    assert e2e == ["fleet_gi_per_s", "setup_s"]


def test_configuration_builds_the_published_associativity():
    cfg = harness.load_json(harness.config_path("skylake_sp_t1"))
    plat = harness.build_platform(cfg)
    pub = cfg["published"]
    assert plat.name == "bench.skylake_sp_t1"
    assert plat.l2.n_ways == pub["l2"]["n_ways"] == 16
    assert plat.llc.n_ways == plat.llc_ways_total == 11
    assert pub["llc"]["n_ways"] == 11
    assert plat.llc.n_slices == plat.llc_slices_total == 2
    assert plat.inclusion == cfg["inclusion"] == pub["inclusion"]
    assert (plat.l2.n_sets, plat.llc.n_sets) == (256, 512)
    assert (plat.n_domains, plat.cores_per_domain) == (1, 2)
    assert plat.provisioning == "shared" and plat.votes == 1
    # the co-tenant joins in the run, not at boot
    assert plat.noise == ()


def test_sound_run_is_correct_and_control_is_not():
    res, verdict = _run(control=True)
    r = res["readings"]
    assert verdict["correct"], verdict
    assert res["run"].n_units > 0
    assert r["engine_calls"] > 0 and r["abstraction_checked"] > 0
    assert r["intervals_checked"] >= res["run"].n_units
    assert r["control.engine_mismatch"] > 0
    assert max(r["control.rate_gap"], r["control.view_gap"]) > \
        checks.LIMITS["rate_gap"]
    for name in ("monitor.dispatches", "monitor.syncs",
                 "monitor.cotenant_accesses"):
        assert harness.load_metric(name)(res["run"]) > 0, name


# -- planted faults -----------------------------------------------------------

def _skewed_rates(orig):
    def broken(self, frac, window_ms):
        snap = orig(self, frac, window_ms)
        snap.rate *= 1.01
        return snap
    return broken


def _one_color_rate_altered(orig):
    def broken(self):
        rates = orig(self)
        if rates:
            first = sorted(rates)[0]
            rates[first] = rates[first] * 1.01 + 1e-3
        return rates
    return broken


def _latency_flipped(orig):
    def broken(state, geom, *args):
        lats = np.array(orig(state, geom, *args))
        lats[(0,) * lats.ndim] ^= 1
        return jnp.asarray(lats)
    return broken


def _wrong_color_filter(orig):
    """The session hands out its first color filter in its second's
    place: two filters of one true color."""
    def broken(self):
        view = orig(self)
        filters = list(view.filters.filters)
        filters[1] = filters[0]
        return types.SimpleNamespace(
            filters=types.SimpleNamespace(filters=filters))
    return broken


def _targets():
    from repro.core import cachesim, vscan
    from repro.core.abstraction import CacheXSession
    return {
        "rate_gap": (vscan.VScan, "_finish_interval", _skewed_rates),
        "view_gap": (vscan.VScan, "per_color_rate",
                     _one_color_rate_altered),
        "engine_mismatch": (cachesim, "access_streams_batched",
                            _latency_flipped),
        "abstraction_faults": (CacheXSession, "colors",
                               _wrong_color_filter),
    }


@pytest.mark.parametrize("number", ["abstraction_faults", "engine_mismatch",
                                    "rate_gap", "view_gap"])
def test_planted_fault_is_caught(number, monkeypatch):
    """The fault is live only once the window is open, so set-up stays
    sound and the run reaches its checks, where its own number is past
    its limit."""
    mod, name, make = _targets()[number]
    orig = getattr(mod, name)
    broken = make(orig)
    live = {"on": False}

    def call(*args, **kw):
        return (broken if live["on"] else orig)(*args, **kw)

    monkeypatch.setattr(mod, name, call)
    open_window = bench.Window.open

    def open_and_break(self):
        open_window(self)
        live["on"] = True

    monkeypatch.setattr(bench.Window, "open", open_and_break)
    res, verdict = _run(seed=12)
    compared = verdict["compared"]
    assert not verdict["correct"], res["readings"]
    assert compared[number]["value"] > compared[number]["limit"]
    assert compared["window_error"]["value"] == 0


def test_control_in_the_programs_place_is_not_correct():
    """``control.py --in-place``: the 16-bit reference engine put in the
    program's place for the window."""
    from benchmarks.chip import control
    undo = control.in_place(jax)
    try:
        res, verdict = _run(seed=14)
    finally:
        undo()
    assert res["readings"]["engine_mismatch"] > 0
    assert not verdict["correct"]


@pytest.mark.parametrize("name", METRICS)
def test_metric_reads_nothing_without_its_record(name):
    run = harness.Run(seed=1, t_process=0.0)
    assert harness.load_metric(name)(run) is None
    # a window with units but without the metric's record
    run.t_window, run.t_close = 1.0, 2.0
    run.units = [(1.0, 1.5, 1)]
    run.counters["traced_units"] = 1
    assert harness.load_metric(name)(run) is None
