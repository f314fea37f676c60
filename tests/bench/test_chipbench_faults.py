"""``correct`` at a size a test run can hold: a sound run of each traffic
kind is correct, the control (the reference put in the program's place
one precision lower) is not, and each fault the cells can have, planted
under the timed path, turns ``correct`` false.

These drive the whole run on the CPU (``run_cell``: set-up, window,
checks) and skip only the harness's look for a chip."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import checks
from benchmarks.chip import run as bench
from repro.core import cachesim

HERE = os.path.dirname(os.path.abspath(__file__))
CFG = json.load(open(os.path.join(HERE, "data", "tiny.json")))
TRAFFIC = {
    "attach": {"kind": "attach", "hosts": 2, "guest_pages": 2048,
               "sample_skip": 2, "trace_units": 1, "warm_shapes": {}},
    "fleet": {"kind": "fleet", "guests": 8,
              "fleet": {"policy": "cas", "cap": "on",
                        "thresholds": [1.0, 4.0], "stream_len": 16,
                        "ws_pages": 2, "warmup": 1},
              "warmup_intervals": 1, "followed": 2, "sample_skip": 2,
              "trace_units": 1, "warm_shapes": {}},
}

SECONDS = {"attach": 4.0, "fleet": 3.0}


@pytest.fixture(autouse=True)
def _registry_restored(monkeypatch):
    """The harness registers the test platform by name (the fleet's clones
    look their donor's platform up); keep that out of later tests."""
    from repro.core import platforms
    monkeypatch.setattr(platforms, "_REGISTRY", dict(platforms._REGISTRY))


def _run(kind, seed=11, control=False):
    res = bench.run_cell(CFG, TRAFFIC[kind], seed,
                         SECONDS[kind], False, "", None, jax,
                         control=control)
    return res, bench.judge(res)


@pytest.mark.parametrize("kind", sorted(TRAFFIC))
def test_sound_run_is_correct_and_control_is_not(kind):
    res, verdict = _run(kind, control=True)
    readings = res["readings"]
    assert verdict["correct"], verdict
    assert readings["window_error"] == 0
    assert readings["engine_calls"] > 0
    over = [k for k in res["numbers"]
            if f"control.{k}" in readings
            and readings[f"control.{k}"] > checks.LIMITS[k]]
    assert over, readings


# -- planted faults -----------------------------------------------------------

def _state_unchanged(orig):
    def broken(state, geom, *args):
        work = jax.tree_util.tree_map(jnp.copy, state)
        _, lats = orig(work, geom, *args)
        return state, lats
    return broken


def _half_left_out(orig):
    """The second half of the batch's real entries (lanes, or guests of a
    multi-guest call) returns nothing."""
    def broken(state, geom, blocks, *args):
        lats = np.array(orig(state, geom, blocks, *args))
        real = np.flatnonzero((np.asarray(blocks) >= 0).reshape(
            blocks.shape[0], -1).any(axis=1))
        lats[real[len(real) // 2:]] = 0
        return jnp.asarray(lats)
    return broken


def _answer_altered(orig):
    def broken(state, geom, *args):
        lats = np.array(orig(state, geom, *args))
        lats[(0,) * lats.ndim] += 36
        return jnp.asarray(lats)
    return broken


FAULTS = {
    ("attach", "state_unchanged"): ("access_stream", _state_unchanged),
    ("attach", "half_left_out"): ("access_streams_batched", _half_left_out),
    ("attach", "answer_altered"): ("access_streams_batched",
                                   _answer_altered),
    ("fleet", "state_unchanged"): ("access_streams_committed",
                                   _state_unchanged),
    ("fleet", "half_left_out"): ("access_streams_batched_multi",
                                 _half_left_out),
    ("fleet", "answer_altered"): ("access_streams_batched_multi",
                                  _answer_altered),
}


@pytest.mark.parametrize("kind,fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(kind, fault, monkeypatch):
    """The fault is live only while the window is open, so set-up stays
    sound and the run reaches its checks."""
    engine, make = FAULTS[(kind, fault)]
    orig = getattr(cachesim, engine)
    broken = make(orig)
    live = {"on": False}

    def engine_call(*args):
        return (broken if live["on"] else orig)(*args)

    monkeypatch.setattr(cachesim, engine, engine_call)
    open_window = bench.Window.open

    def open_and_break(self):
        open_window(self)
        live["on"] = True

    monkeypatch.setattr(bench.Window, "open", open_and_break)
    res, verdict = _run(kind, seed=12)
    assert not verdict["correct"], res["readings"]


def test_fleet_progress_in_bfloat16_is_not_correct(monkeypatch):
    """The fleet's floating-point part: the progress model run one
    precision lower is caught by ``progress_gap``."""
    from repro.core import fleet
    orig = fleet.fleet_interval_progress

    def low(*args, **kw):
        args = [a.astype(jnp.bfloat16)
                if hasattr(a, "dtype") and a.dtype == jnp.float32 else a
                for a in args]
        return tuple(x.astype(jnp.float32) for x in orig(*args, **kw))

    monkeypatch.setattr(fleet, "fleet_interval_progress", low)
    res, verdict = _run("fleet", seed=13)
    readings = res["readings"]
    assert readings["progress_gap"] > checks.LIMITS["progress_gap"]
    assert not verdict["correct"]


def test_control_in_the_programs_place_is_not_correct():
    """``control.py --in-place``: the 16-bit reference engine put in the
    program's place for the window; the attach built on it is refuted."""
    from benchmarks.chip import control
    undo = control.in_place(jax)
    try:
        res, verdict = _run("attach", seed=14)
    finally:
        undo()
    assert not verdict["correct"]
    assert res["readings"]["engine_mismatch"] > 0


def _live_in_window(monkeypatch, mod, name, make):
    """Replace ``mod.name`` by ``make(original)`` while the window is
    open."""
    orig = getattr(mod, name)
    broken = make(orig)
    live = {"on": False}
    monkeypatch.setattr(mod, name,
                        lambda *a, **kw: (broken if live["on"] else orig)(
                            *a, **kw))
    open_window = bench.Window.open

    def open_and_break(self):
        open_window(self)
        live["on"] = True

    monkeypatch.setattr(bench.Window, "open", open_and_break)


def test_fleet_results_handed_to_the_wrong_guests_are_not_correct(
        monkeypatch):
    """The lockstep executor swaps guest 0's results with those of the
    first guest whose measurements differ from them: every engine call
    is right, and only the delivery check can see it.  The clones of one
    boot measure alike, so guest 1 gets a co-tenant of its own."""
    from repro.core import fleet, probeplan
    from repro.core.platforms import NoiseSpec

    build = fleet.ShardedFleet.__init__

    def with_a_noisy_guest(self, *args, **kw):
        build(self, *args, **kw)
        self.sims[1].host.add_cotenant(NoiseSpec(
            name="extra_polluter", domain=1, rate_per_ms=30.0,
            kind="polluter", region_pages=256).workload())

    monkeypatch.setattr(fleet.ShardedFleet, "__init__", with_a_noisy_guest)

    def differ(a, b):
        return a.last is not None and any(
            not np.array_equal(x, y) for x, y in zip(a.last, b.last))

    def swap(orig):
        def broken(vms, plans):
            out = list(orig(vms, plans))
            j = next((j for j in range(1, len(out))
                      if differ(out[0], out[j])), 0)
            out[0], out[j] = out[j], out[0]
            return out
        return broken

    _live_in_window(monkeypatch, probeplan, "execute_many", swap)
    monkeypatch.setitem(TRAFFIC["fleet"], "followed", 8)
    res, verdict = _run("fleet", seed=18)
    readings = res["readings"]
    assert readings["engine_mismatch"] == 0
    assert readings["delivery_faults"] > 0, readings
    assert not verdict["correct"]


def test_fleet_skewed_set_rates_are_not_correct(monkeypatch):
    """The monitor keeps each set's rate 1% high: caught by ``rate_gap``
    against the reference's threshold count."""
    from repro.core import vscan

    def skew(orig):
        def broken(self, frac, window_ms):
            snap = orig(self, frac, window_ms)
            snap.rate *= 1.01
            return snap
        return broken

    _live_in_window(monkeypatch, vscan.VScan, "_finish_interval", skew)
    res, verdict = _run("fleet", seed=19)
    assert res["readings"]["rate_gap"] > checks.LIMITS["rate_gap"]
    assert not verdict["correct"]


def test_fleet_placement_that_ignores_the_tiers_is_not_correct(
        monkeypatch):
    """CAS placing every task on the most contended domain's vCPUs is
    caught by ``placement_faults``."""
    from repro.core import fleet

    def worst(orig):
        def broken(policy, idle, vcpu_domain, tiers, prev, rr_index=0):
            idle = sorted(idle)
            return max(idle, key=lambda v: (tiers.get(vcpu_domain[v], 0),
                                            -v))
        return broken

    _live_in_window(monkeypatch, fleet, "policy_place", worst)
    res, verdict = _run("fleet", seed=20)
    assert res["readings"]["placement_faults"] > 0, res["readings"]
    assert not verdict["correct"]
