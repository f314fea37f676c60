"""CacheShield-style attack detection: the three-way taxonomy, proven.

Property-based (via ``tests/_hypothesis_compat``) over the classifier's
input space plus a labeled-fixture differential test:

  * **benign never classifies attack** — randomized honest-load traces
    (sub-burst contention at any intensity, broad saturation storms,
    transient whole-set spikes) never produce an attack onset: FPR 0
    across the sampled space;
  * **attacks detect within a bounded window** — a concentrated
    persistent burst overlay on any benign background raises the onset
    within an analytically-derived window bound;
  * **drift-shaped traces stay benign** — a CAT way shrink self-conflicts
    every set at (w_old-w_new)/w_old < high_frac, so the shield leaves it
    to VSCAN's drift machinery (attack != drift in both directions);
  * **differential fixture** — traces recorded from the real simulator
    (benign co-tenant load, and an `AttackerGuest` episode) replay
    through `classify_trace` to exactly the labels/onsets recorded in
    ``tests/data/shield_traces.json``.
"""

import json
import os

import numpy as np

from repro.core.shield import (CacheShield, HIGH_FRAC, MAX_ATTACK_FRAC,
                               MIN_WINDOWS, THRESHOLD, classify_trace)
from tests._hypothesis_compat import given, settings, st

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "shield_traces.json")


# ---------------------------------------------------------------------------
# synthetic trace generators (the benign families the docstring claims)
# ---------------------------------------------------------------------------

def _benign_trace(rng, n_sets, n_windows, storm_p, spike_p):
    """Honest-load traces: per-set contention anywhere below the burst
    threshold, broad saturation storms (every set bursts — the background
    absorbs them), and transient concentrated spikes (max 2 consecutive
    burst windows per set, then >= 2 quiet ones — honest load does not
    *sustain* whole-set eviction of the same few sets)."""
    fracs = []
    spike_run = np.zeros(n_sets, int)     # consecutive burst windows
    cooldown = np.zeros(n_sets, int)      # enforced quiet windows left
    for _ in range(n_windows):
        if rng.random() < storm_p:
            f = rng.uniform(HIGH_FRAC, 1.0, n_sets)   # broad storm: all burst
            spike_run[:] = 0
            cooldown[:] = 2
        else:
            f = rng.uniform(0.0, HIGH_FRAC - 0.02, n_sets)
            spike = (rng.random(n_sets) < spike_p) & (cooldown == 0)
            spike &= spike_run < 2
            f[spike] = rng.uniform(HIGH_FRAC, 1.0, int(spike.sum()))
            spike_run = np.where(spike, spike_run + 1, 0)
            cooldown = np.maximum(0, cooldown - 1)
            cooldown[(spike_run == 2)] = 2
        fracs.append(f)
    return fracs


@settings(max_examples=30)
@given(n_sets=st.integers(4, 32), n_windows=st.integers(6, 40),
       storm_p=st.floats(0.0, 0.5), spike_p=st.floats(0.0, 0.3),
       seed=st.integers(0, 10**6))
def test_benign_traces_never_classify_attack(n_sets, n_windows, storm_p,
                                             spike_p, seed):
    rng = np.random.default_rng(seed)
    out = classify_trace(_benign_trace(rng, n_sets, n_windows,
                                       storm_p, spike_p))
    assert out["detected"] is False
    assert out["onsets"] == 0
    assert "attack" not in out["labels"][:MIN_WINDOWS - 1]  # trivially too


@settings(max_examples=30)
@given(n_sets=st.integers(6, 32), start=st.integers(0, 10),
       base=st.floats(0.0, 0.5), seed=st.integers(0, 10**6))
def test_attacks_detect_within_bounded_windows(n_sets, start, base, seed):
    """Concentrated persistent whole-set bursts (<= the concentration
    limit) over any sub-burst background must raise the onset within the
    analytic bound: score grows >= 1 - max_attack_frac - slack per
    window, so threshold/0.41 (~5) windows to alarm + min_windows."""
    rng = np.random.default_rng(seed)
    limit = max(1, int(MAX_ATTACK_FRAC * n_sets))
    k = int(rng.integers(1, limit + 1))
    targets = rng.choice(n_sets, size=k, replace=False)
    n_windows = start + 12
    fracs = []
    for w in range(n_windows):
        f = rng.uniform(0.0, base, n_sets)
        if w >= start:
            f[targets] = rng.uniform(0.96, 1.0, k)
        fracs.append(f)
    out = classify_trace(fracs)
    assert out["detected"] is True
    bound = int(np.ceil(THRESHOLD / 0.41)) + MIN_WINDOWS + 1
    assert start <= out["detect_window"] <= start + bound


@settings(max_examples=20)
@given(n_sets=st.integers(4, 32), shrink=st.sampled_from([0.25, 1 / 3, 0.5]),
       seed=st.integers(0, 10**6))
def test_cat_drift_shape_is_not_attack(n_sets, shrink, seed):
    """A CAT repartition self-conflicts *every* live set at the capacity
    loss fraction — below high_frac and population-wide; the shield must
    stay out of VSCAN's drift lane."""
    rng = np.random.default_rng(seed)
    fracs = [rng.uniform(0, 0.1, n_sets) for _ in range(3)]
    fracs += [np.full(n_sets, shrink) + rng.uniform(0, 0.05, n_sets)
              for _ in range(10)]
    out = classify_trace(fracs)
    assert out["detected"] is False
    assert all(l == "benign" for l in out["labels"])


def test_broad_saturation_is_broad_not_attack():
    """A domain-wide pollution storm saturates most of the population:
    the background mean kills CUSUM growth, so nothing ever alarms."""
    n = 16
    fracs = [np.full(n, 0.97) for _ in range(20)]
    out = classify_trace(fracs)
    assert out["detected"] is False
    assert "attack" not in out["labels"]


def test_streaming_onset_and_clear_transitions():
    """One episode: onset fires once (not per window), `under_attack`
    holds through the episode, and the cleared transition arrives after
    `clear_windows` quiet windows."""
    n, targets = 8, [2, 5]
    sh = CacheShield(n)
    onsets = clears = 0
    for w in range(24):
        f = np.full(n, 0.1)
        if 4 <= w < 14:
            f[targets] = 1.0
        v = sh.observe_frac(f, time_ms=float(w))
        onsets += v.onset is not None
        clears += v.cleared
    assert onsets == 1 and clears == 1
    assert sh.signals[0].kind == "prime_probe"
    assert set(sh.signals[0].set_indices) == set(targets)
    assert not sh.under_attack and not sh.attacked


def test_population_resize_resets_scores():
    sh = CacheShield(8)
    f = np.full(8, 0.1); f[1] = 1.0
    for _ in range(3):
        sh.observe_frac(f)
    assert sh.score.max() > 0
    v = sh.observe_frac(np.full(12, 0.1))     # monitor rebuilt mid-stream
    assert len(sh.score) == 12 and sh.score.max() == 0.0
    assert v.label == "benign"


def test_labeled_fixture_differential():
    """Traces recorded from the real simulator (the fixture's
    ``provenance`` says how) must replay through `classify_trace`
    to exactly the recorded verdicts — any classifier change that moves
    these labels is a behavior change, not a refactor."""
    with open(FIXTURE) as f:
        fx = json.load(f)
    assert set(fx) == {"provenance", "benign", "attack"}
    for name in ("benign", "attack"):
        rec = fx[name]
        out = classify_trace([np.array(w, float) for w in rec["fracs"]])
        assert out == rec["expected"], name
    assert fx["benign"]["expected"]["detected"] is False
    assert fx["attack"]["expected"]["detected"] is True
    assert fx["attack"]["expected"]["labels"].count("attack") >= MIN_WINDOWS
