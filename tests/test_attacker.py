"""Adversarial co-tenancy end to end: attack, detect, defend.

Covers the tentpole from the attacker's side of the machine:

  * `AttackerGuest` mechanics: a second guest on the victim's `SimHost`
    pays a real attach, profiles victim-hot cells without hypercalls
    (the victim's own priming overwrites the attacker's lines), and its
    Prime+Probe / Evict+Time windows compile through ProbePlan under the
    ``attack.*`` label namespace;
  * the detection loop: a live attack raises `AttackSignal` via
    `CacheXSession.subscribe_attack`, quarantines exactly the attacked
    sets out of the CAS/CAP aggregates, and — the taxonomy's core claim —
    never raises a `DriftSignal` or triggers a repair (attack != drift),
    on every registered platform;
  * the un-quarantine regression (satellite): `VScan.flagged` used to be
    one-way outside of rebuilds, so attack-quarantined (structurally
    intact) sets stayed dead forever after the attacker stopped;
    `confirm_clean()` now lifts them while genuinely broken sets stay
    flagged;
  * drift mid-attack: a remap landing while the attack runs is still
    caught and repaired at the usual >= 5x-cheaper-than-reattach cost —
    the attack quarantine must not block or inflate real repairs;
  * the closed defense loop: `FleetSim(attack=True)` detects, schedules
    the CAT way-isolation host event, recovers through the normal
    drift-repair path, and the sensitive task's quiet-domain residency
    is no worse after the episode than before it.
"""

import dataclasses

import numpy as np
import pytest

from repro.core import (AttackerGuest, CacheXSession, ProbeConfig,
                        attack_gen, get_platform, list_platforms)
from repro.core.fleet import FleetSim
from repro.core.host_model import HostEvent
from tests._hypothesis_compat import given, settings, st

FAST_PLATFORM = "skylake_sp"


def _matrix_params():
    return [name if name == FAST_PLATFORM
            else pytest.param(name, marks=pytest.mark.slow)
            for name in list_platforms()]


def _attach_victim(name, seed):
    plat = get_platform(name)
    host, vm = plat.make_host_vm(seed=seed)
    # prune_self_conflicts is the production posture on few-row
    # geometries (milan_ccx): monitor pairs that thrash *each other*
    # zero-wait would hand confirm_drift structural false positives the
    # moment anything (e.g. an attack) builds their suspicion streak.
    session = CacheXSession.attach(
        vm, plat, ProbeConfig.for_platform(plat, seed=seed,
                                           prune_self_conflicts=True))
    session.monitored_sets()
    return plat, host, vm, session


def _concentrated_k(session):
    """Largest target count the shield still calls concentrated."""
    n = len(session.monitored_sets())
    return max(1, int(0.34 * n) - 0)


# ---------------------------------------------------------------------------
# attacker mechanics
# ---------------------------------------------------------------------------

def test_attacker_boots_and_pays_attach():
    plat, host, vm, session = _attach_victim(FAST_PLATFORM, 0)
    atk = AttackerGuest(host, plat, seed=0)
    assert atk.vm is not vm and atk.vm.host is host
    assert atk.attach_dispatches > 0
    assert len(atk._sets()) > 0


def test_profile_ranks_victim_monitored_cells_hot():
    """Prime-all / victim-runs / probe-all: the cells the victim's VSCAN
    primes every window come back fully evicted, so the top of the
    activity ranking finds the victim without any hypercall."""
    plat, host, vm, session = _attach_victim(FAST_PLATFORM, 0)
    atk = AttackerGuest(host, plat, seed=0)
    act = atk.profile(rounds=2, between=lambda: session.refresh())
    victim_cells = {vm.hypercall_llc_setslice(int(m.es.gvas[0]))
                    for m in session.monitored_sets()}
    own = [atk.vm.hypercall_llc_setslice(int(m.es.gvas[0]))
           for m in atk._sets()]
    shared = [i for i, c in enumerate(own) if c in victim_cells]
    assert shared, "attacker and victim must share monitored cells"
    assert np.all(act[shared] >= 0.8), "victim priming ~= full eviction"
    k = _concentrated_k(session)
    targets = atk.choose_targets(k=k)
    assert len(targets) == k
    # the chosen targets are the victim-active cells
    assert set(targets) <= set(np.flatnonzero(act >= 0.9 - 1e-9)) | set(shared)


@pytest.mark.parametrize("variant", ["primeprobe", "evicttime"])
def test_attack_windows_observe_victim_activity(variant):
    """Windowed Prime+Probe and flush-less Evict+Time both read the
    victim: quiet windows show nothing, windows the victim probes
    through show activity on the shared targets."""
    plat, host, vm, session = _attach_victim(FAST_PLATFORM, 1)
    atk = AttackerGuest(host, plat, seed=1, variant=variant)
    atk.profile(rounds=2, between=lambda: session.refresh())
    atk.choose_targets(k=2)
    quiet = atk.observe(window_ms=3.0)        # victim idle: no refresh
    assert not any(quiet.victim_active)
    atk.prime()
    session.refresh()                          # victim primes its cells
    busy = atk.probe()
    assert np.max(busy) >= 0.5, "victim priming must be visible"
    plan = atk.window_plan(3.0)
    assert plan.label == f"attack.{variant}"
    rep = atk.report()
    assert rep.windows == 1 and rep.attack_dispatches > 0


@settings(max_examples=15)
@given(n_blocks=st.integers(1, 64), n=st.integers(1, 512),
       seed=st.integers(0, 10**6))
def test_attack_gen_sweeps_every_target_deterministically(n_blocks, n, seed):
    """The attack stream is a deterministic in-order sweep: every target
    block recurs with period len(blocks) (whole-set re-prime guarantee),
    independent of the rng the host hands co-tenant generators."""
    blocks = np.arange(100, 100 + n_blocks, dtype=np.int64)
    gen = attack_gen(blocks)
    a = gen(np.random.default_rng(seed), n)
    b = gen(np.random.default_rng(seed + 1), n)
    assert len(a) == n and np.array_equal(a, b)
    assert np.array_equal(a, np.tile(blocks, -(-n // n_blocks))[:n])


# ---------------------------------------------------------------------------
# detect: attack raises AttackSignal, never DriftSignal
# ---------------------------------------------------------------------------

def _run_attack_episode(name, seed, windows=8, k=None):
    plat, host, vm, session = _attach_victim(name, seed)
    drifts, attacks = [], []
    session.subscribe_drift(drifts.append)
    session.subscribe_attack(attacks.append)
    atk = AttackerGuest(host, plat, seed=seed)
    atk.profile(rounds=2, between=lambda: session.refresh())
    atk.choose_targets(k=k if k is not None else _concentrated_k(session))
    atk.begin()
    for _ in range(windows):
        session.refresh()
    return plat, host, vm, session, atk, drifts, attacks


def test_attack_detected_and_quarantined_then_cleared():
    (plat, host, vm, session, atk,
     drifts, attacks) = _run_attack_episode(FAST_PLATFORM, 0)
    assert attacks, "sustained concentrated bursts must raise AttackSignal"
    sig = attacks[0]
    assert sig.kind == "prime_probe" and sig.windows >= 2
    vs = session._vs
    flagged = set(np.flatnonzero(vs.flagged))
    assert flagged == set(sig.set_indices)
    assert set(np.flatnonzero(vs.attack_flagged)) == flagged
    # quarantined garbage stays out of the published aggregates
    view = session.refresh()
    live_doms = {m.domain for i, m in enumerate(session.monitored_sets())
                 if i not in flagged}
    assert set(view.per_domain) <= live_doms | set(view.per_domain)
    # the taxonomy holds: no DriftSignal, nothing for repair to do
    assert drifts == []
    assert not session.check_drift()["any_broken"]
    rep = session.repair()
    assert not rep.anything_broken, "attack quarantine must not force repairs"
    # attacker stops -> shield clears -> quarantine lifts (satellite (c))
    atk.stop()
    for _ in range(6):
        session.refresh()
    assert not session.shield.under_attack
    assert not vs.flagged.any() and not vs.attack_flagged.any()
    assert drifts == []


@pytest.mark.parametrize("name", _matrix_params())
def test_attack_is_never_drift_on_any_platform(name):
    """Regression matrix (satellite (b)): a live attacker on every
    registered platform produces zero false DriftSignals and zero
    spurious (non-attack) quarantines."""
    (plat, host, vm, session, atk,
     drifts, attacks) = _run_attack_episode(name, 3, windows=6)
    vs = session._vs
    assert drifts == [], f"{name}: attack must not masquerade as drift"
    spurious = np.flatnonzero(vs.flagged & ~vs.attack_flagged)
    assert spurious.size == 0, f"{name}: only attack quarantines allowed"
    assert not session.check_drift()["any_broken"]


def test_drift_mid_attack_still_repairs_cheaply():
    """A remap landing *while the attack runs* must still be caught by
    the drift machinery and repaired >= 5x cheaper than re-attaching —
    the attack quarantine neither hides real damage nor lets the
    attacker inflate repair cost (attack-flagged sets are excluded from
    the forced-broken mask)."""
    (plat, host, vm, session, atk,
     drifts, attacks) = _run_attack_episode(FAST_PLATFORM, 0)
    assert attacks and not drifts
    attach_dispatches = vm.stat_passes
    host.schedule_event(HostEvent(at_ms=host.time_ms + 0.5,
                                  kind="remap", fraction=0.25))
    vm.wait_ms(1.0)
    assert session.check_drift()["any_broken"], \
        "real damage must stay visible through the attack"
    d0 = vm.stat_passes
    rep = session.repair()
    repair_dispatches = vm.stat_passes - d0
    assert rep.anything_broken
    assert repair_dispatches * 5 <= attach_dispatches, (
        f"repair {repair_dispatches} vs attach {attach_dispatches}")
    assert not session.validate()["stale"]


# ---------------------------------------------------------------------------
# the un-quarantine regression (VScan.confirm_clean)
# ---------------------------------------------------------------------------

def test_vscan_quarantine_is_no_longer_one_way():
    """The latent bug this PR fixes: `flagged` was one-way outside of
    `replace_set`, so interference-quarantined sets never came back.
    `confirm_clean()` re-checks zero-wait and lifts intact sets, while a
    genuinely broken set (CAT capacity loss self-conflicts even with no
    co-tenant traffic) stays flagged."""
    plat, host, vm, session = _attach_victim(FAST_PLATFORM, 5)
    vs = session._vs
    vs.flag_sets([0, 2], attack=True)
    vs.flag_sets([1])
    assert set(np.flatnonzero(vs.flagged)) == {0, 1, 2}
    lifted = vs.confirm_clean()
    assert set(lifted) == {0, 1, 2}, "intact sets must all come back"
    assert not vs.flagged.any() and not vs.attack_flagged.any()
    # now break the cache for real: way shrink self-conflicts every set
    vs.flag_sets(range(len(vs.monitored)), attack=True)
    host.schedule_event(HostEvent(at_ms=host.time_ms + 0.1,
                                  kind="cat", new_llc_ways=4))
    vm.wait_ms(0.2)
    lifted = vs.confirm_clean()
    assert lifted == ()
    assert vs.flagged.all(), "broken sets must stay quarantined"


@pytest.fixture(scope="module")
def healthy_victim():
    """One victim attached on a healthy cache, shared by the property's
    examples: an attach takes seconds, an example must not."""
    return _attach_victim(FAST_PLATFORM, 7)


@settings(max_examples=8)
@given(idxs=st.lists(st.integers(0, 7), min_size=1, max_size=8),
       attack=st.booleans())
def test_confirm_clean_lifts_any_intact_quarantine(healthy_victim, idxs,
                                                   attack):
    """Property form: whatever subset is quarantined on a healthy cache,
    one `confirm_clean()` lifts all of it and resets suspicion."""
    plat, host, vm, session = healthy_victim
    vs = session._vs
    # every example starts with nothing quarantined or suspected
    vs.flagged[:] = False
    vs.attack_flagged[:] = False
    vs._suspect[:] = 0
    idxs = sorted({i % len(vs.monitored) for i in idxs})
    vs.flag_sets(idxs, attack=attack)
    assert set(vs.confirm_clean()) == set(idxs)
    assert not vs.flagged.any()
    assert all(vs._suspect[i] == 0 for i in idxs)


# ---------------------------------------------------------------------------
# defend: the closed fleet loop
# ---------------------------------------------------------------------------

def test_fleet_attack_defense_closed_loop():
    """FleetSim(attack=True): detect -> sustain -> CAT way isolation ->
    DriftSignal from the re-carve -> repair + rebucket -> residency
    recovers.  Zero false drift throughout (the acceptance gate)."""
    sim = FleetSim(FAST_PLATFORM, attack=True, with_poisoner=False,
                   n_intervals=18)
    rep = sim.run()
    assert rep.attack_windows > 0
    assert rep.attack_detected and rep.attack_detect_intervals >= 0
    assert rep.defenses == 1
    assert rep.false_drift == 0
    assert rep.repairs >= 1, "the defensive re-carve must repair through"
    assert rep.residency_post >= rep.residency_pre
    assert sim.host.geom.llc.n_ways == sim.plat.attack.isolate_ways
    assert sim.attacker is not None and not sim.attacker.active


def test_fleet_undefended_attack_and_benign_fields():
    """defend=False keeps the episode open (no CAT event, attacker still
    live) while detection and the zero-false-drift invariant hold; a
    benign run reports zeroed adversarial fields."""
    sim = FleetSim(FAST_PLATFORM, attack=True, defend=False,
                   with_poisoner=False, n_intervals=14)
    rep = sim.run()
    assert rep.attack_detected and rep.defenses == 0
    assert rep.false_drift == 0
    assert sim.attacker.active, "nobody stopped the attacker"
    benign = FleetSim(FAST_PLATFORM, n_intervals=6).run()
    assert benign.attack_windows == 0 and not benign.attack_detected
    assert benign.defenses == 0 and benign.false_drift == 0
    assert benign.residency_pre == benign.residency_post == 0.0
