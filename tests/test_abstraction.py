"""CacheXSession — the first-class cache-abstraction query API.

Covers the tentpole end to end:
  * `ProbeConfig` platform defaults (votes / prime_reps / pool sizing with
    the documented cap) and per-call overrides;
  * lazy attach: stages probe on first query, at most once;
  * attach → query → export → reboot → import_ parity on every registered
    platform (hypercall-validated, zero re-probing on import; only the
    tier-1 platform runs by default, the rest are `slow`);
  * contention staleness metadata, interval-driven re-probe, and
    subscribe/unsubscribe publication to CAS/CAP-style consumers;
  * the `run_cachex` burst-cotenant cleanup regression (satellite bugfix)
    and the *removal* of the deprecated stage-builder shims;
  * the public-API snapshot of `repro.core` (fails when the exported
    surface changes without updating tests/data/core_api_snapshot.txt).
"""

import csv
import dataclasses
import io
import json
import os

import numpy as np
import pytest

import repro.core as core
from repro.core import (CacheXSession, ProbeConfig, get_platform,
                        list_platforms, run_cachex)
from repro.core.abstraction import VSCAN_POOL_CAP_PAGES
from repro.core.eviction import C_POOL_SCALE
from repro.core.host_model import CotenantWorkload, polluter_gen
from repro.core.runner import CacheXReport

FAST_PLATFORM = "skylake_sp"   # tier-1; the rest of the matrix is `slow`
SNAPSHOT_PATH = os.path.join(os.path.dirname(__file__), "data",
                             "core_api_snapshot.txt")


def _matrix_params():
    return [name if name == FAST_PLATFORM
            else pytest.param(name, marks=pytest.mark.slow)
            for name in list_platforms()]


# ---------------------------------------------------------------------------
# ProbeConfig
# ---------------------------------------------------------------------------

def test_probe_config_platform_defaults_and_overrides():
    shared = ProbeConfig.for_platform("skylake_shared")
    assert shared.votes == get_platform("skylake_shared").votes == 3
    cfg = ProbeConfig.for_platform("skylake_sp")
    assert (cfg.votes, cfg.prime_reps) == (1, 1)
    over = ProbeConfig.for_platform("skylake_sp", votes=5, f=4)
    assert over.votes == 5 and over.f == 4
    assert over.replace(seed=9).seed == 9
    with pytest.raises(dataclasses.FrozenInstanceError):
        cfg.votes = 2


def test_vscan_pool_sizing_is_platform_derived_and_capped():
    """The old magic `min(..., 384)` now lives in ProbeConfig: the §3.1
    Ps = W*rows*slices*C sizing, capped at VSCAN_POOL_CAP_PAGES (384 ==
    Ps of the largest registered geometry, so the cap is inactive on
    every shipped platform and only binds beyond it)."""
    for name in list_platforms():
        plat = get_platform(name)
        cfg = ProbeConfig.for_platform(plat)
        ps = (plat.effective_ways * plat.n_llc_rows_per_offset
              * plat.llc.n_slices * C_POOL_SCALE)
        assert cfg.vscan_pool_pages == min(ps, VSCAN_POOL_CAP_PAGES), name
        assert cfg.vscan_pool_pages <= VSCAN_POOL_CAP_PAGES, name
    # skylake_sp *is* the sizing's origin: Ps == cap exactly
    assert ProbeConfig.for_platform("skylake_sp").vscan_pool_pages == 384
    # a hypothetical larger geometry hits the cap
    from repro.core.cachesim import CacheGeometry
    big = dataclasses.replace(get_platform("skylake_sp"),
                              llc=CacheGeometry(n_sets=2048, n_ways=16,
                                                n_slices=2))
    assert ProbeConfig().derive_vscan_pool(big) == VSCAN_POOL_CAP_PAGES


# ---------------------------------------------------------------------------
# lazy lifecycle
# ---------------------------------------------------------------------------

def test_attach_is_lazy_and_stages_run_once():
    plat = get_platform(FAST_PLATFORM)
    host, vm = plat.make_host_vm(seed=21)
    session = CacheXSession.attach(vm, plat,
                                   ProbeConfig.for_platform(plat, seed=21))
    assert vm.stat_passes == 0          # nothing probed yet
    session.colors()
    after_colors = vm.stat_passes
    assert after_colors > 0             # VCOL filters were built
    session.colors()                    # second query: no re-probe
    assert vm.stat_passes == after_colors
    session.topology()
    after_topo = vm.stat_passes
    assert after_topo > after_colors    # VEV stage ran
    session.topology()
    assert vm.stat_passes == after_topo


# ---------------------------------------------------------------------------
# attach → query → export → reboot → import_ (whole matrix)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", _matrix_params())
def test_attach_query_export_import_parity(name):
    """The 'persists across reboot' story, per platform: the exported
    abstraction re-attaches to a rebooted VM with zero re-probing and
    reproduces topology()/colors() answers, validated against hypercall
    ground truth (§6.2)."""
    plat = get_platform(name)
    host, vm = plat.make_host_vm(seed=13)
    session = CacheXSession.attach(vm, plat,
                                   ProbeConfig.for_platform(plat, seed=13))
    # color pages before the VEV stage floods the LLC (run_cachex's stage
    # order): on small-LLC geometries (milan_ccx) directory evictions from
    # a full LLC can back-invalidate L2 lines mid-filter and cost accuracy
    pages = vm.alloc_pages(8 * plat.n_l2_colors)
    colored = session.colors().colors_of(pages)
    free_lists = session.colors().build_free_lists(
        vm.alloc_pages(4 * plat.n_l2_colors))
    topo = session.topology()
    assert topo.detected_associativity == plat.effective_ways
    assert topo.n_domains == plat.n_domains
    session.refresh()                       # VSCAN live before export
    truth = session.validate()
    assert truth["ways_match"]
    assert truth["vev_verified"] >= 1
    if plat.l2_filter_reliable and not plat.noise:
        assert truth["vcol_accuracy"] == 1.0
        assert truth["vev_verified"] == truth["vev_built"]

    js = session.export_json()
    vm2 = vm.reboot(seed=14)
    before = vm2.stat_passes
    restored = CacheXSession.import_json(vm2, js)
    assert restored.topology() == topo
    np.testing.assert_array_equal(restored.colors().colors_of(pages),
                                  colored)
    assert vm2.stat_passes == before, "import_ must not re-probe"
    # hypercall ground truth on re-import: identical verdicts
    truth2 = restored.validate()
    assert truth2["vcol_accuracy"] == truth["vcol_accuracy"]
    assert truth2["vev_verified"] == truth["vev_verified"]
    assert truth2["ways_match"]
    # every page the abstraction references — including the colored free
    # lists — is re-reserved: fresh allocations cannot recycle them
    known = ({int(p) for ps in free_lists.values() for p in ps}
             | set(int(p) for p in pages))
    still_free = set(vm2._free_guest_pages)
    assert not known & still_free
    # contention re-measures on the *imported* monitored sets
    assert (len(restored.monitored_sets())
            == len(session.monitored_sets()))
    view = restored.refresh()
    assert view.interval == 1 and vm2.stat_passes > before


def test_llc_backend_is_the_default_and_bit_identical():
    """PR-9 guard: the backend seam must not move the LLC path.  The
    default ``attach()`` and an explicit ``backend="llc"`` (registry
    path) produce the same session type and, on identically-seeded VMs,
    bit-identical exports."""
    from repro.core import get_backend, list_backends

    assert "llc" in list_backends()
    assert get_backend("llc").name == "llc"
    plat = get_platform(FAST_PLATFORM)

    def probed_export(backend_kw):
        host, vm = plat.make_host_vm(seed=77)
        session = CacheXSession.attach(
            vm, plat, ProbeConfig.for_platform(plat, seed=77), **backend_kw)
        assert type(session) is CacheXSession
        session.topology()
        session.colors()
        session.refresh()
        return session.export_json()

    assert probed_export({}) == probed_export({"backend": "llc"})


def test_import_rejects_foreign_payload():
    plat = get_platform(FAST_PLATFORM)
    host, vm = plat.make_host_vm(seed=1)
    with pytest.raises(ValueError):
        CacheXSession.import_(vm, {"format": "something-else"})


def test_import_skips_retired_config_knobs():
    """Snapshots written while ProbeConfig still had the `use_batch` /
    `use_plans` route switches import: the retired keys are dropped and
    every live knob survives."""
    plat = get_platform(FAST_PLATFORM)
    host, vm = plat.make_host_vm(seed=2)
    cfg = ProbeConfig.for_platform(plat, seed=2, f=3)
    data = CacheXSession.attach(vm, plat, cfg).export()
    data["config"].update(use_batch=False, use_plans=False)
    session = CacheXSession.import_(vm.reboot(seed=3), data)
    assert session.config == cfg


def test_reboot_preserves_backing_and_reserve_pages():
    plat = get_platform(FAST_PLATFORM)
    host, vm = plat.make_host_vm(seed=5)
    taken = vm.alloc_pages(16)
    vm2 = vm.reboot(seed=6)
    # GPA→HPA backing identical (the whole point of persistence)
    for p in range(0, vm.n_guest_pages, 997):
        assert vm2.hypercall_hpa_page(p) == vm.hypercall_hpa_page(p)
    # guest-side state is fresh: previously-taken pages are free again...
    assert vm2.stat_passes == 0 and vm2.stat_accesses == 0
    assert len(vm2._free_guest_pages) == vm2.n_guest_pages
    # ...until explicitly re-reserved
    vm2.reserve_pages(taken)
    assert not set(int(p) for p in taken) & set(vm2._free_guest_pages)


# ---------------------------------------------------------------------------
# contention: staleness, interval-driven re-probe, subscriptions
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def live_session():
    plat = get_platform(FAST_PLATFORM)
    host, vm = plat.make_host_vm(seed=33)
    session = CacheXSession.attach(vm, plat,
                                   ProbeConfig.for_platform(plat, seed=33))
    session.monitored_sets()
    return host, vm, session


def test_contention_staleness_drives_reprobe(live_session):
    host, vm, session = live_session
    v1 = session.contention()               # first query probes
    assert v1.interval >= 1
    assert session.contention(max_age_ms=float("inf")) is v1   # pure read
    assert v1.age_ms(vm.host.time_ms) <= session.config.refresh_interval_ms
    vm.wait_ms(session.config.refresh_interval_ms + 1.0)       # goes stale
    v2 = session.contention()               # interval-driven re-probe
    assert v2.interval == v1.interval + 1
    assert v2.measured_at_ms > v1.measured_at_ms
    assert session.contention() is v2       # fresh again: served from cache


def test_subscribers_receive_published_updates(live_session):
    host, vm, session = live_session
    seen = []
    token = session.subscribe(lambda view: seen.append(view))
    burst = CotenantWorkload("sub_burst", 0, 150.0,
                             polluter_gen(region_pages=2048))
    host.add_cotenant(burst)
    v = session.refresh()
    assert seen and seen[-1] is v
    assert set(v.per_domain) == set(session.domain_vcpus())
    assert v.mean_rate > 0.0                # the burst is measurable
    host.remove_cotenant("sub_burst")
    n = len(seen)
    session.unsubscribe(token)
    session.refresh()
    assert len(seen) == n                   # unsubscribed: no more deliveries


def test_subscribe_replay_delivers_last_view(live_session):
    host, vm, session = live_session
    session.contention()
    seen = []
    session.unsubscribe(session.subscribe(seen.append, replay=True))
    assert len(seen) == 1


# ---------------------------------------------------------------------------
# runner integration: burst cleanup + deprecated shims + CSV contract
# ---------------------------------------------------------------------------

def test_run_cachex_removes_measurement_burst():
    """Regression (satellite bugfix): the contention-phase burst cotenant
    must be *removed*, not left disabled, so the CAP stage and any later
    reuse of the host see the platform's own baseline.  A caller-disabled
    cotenant must stay disabled, and a reused VM's report must count only
    this run's probing cost."""
    plat = get_platform(FAST_PLATFORM)
    host, vm = plat.make_host_vm(seed=2)
    sleeper = CotenantWorkload("caller_disabled", 0, 25.0, polluter_gen(),
                               enabled=False)
    host.add_cotenant(sleeper)
    vm.access(vm.gva(0, 0))                 # pre-existing probing activity
    before_passes = vm.stat_passes
    r = run_cachex(plat, seed=2, monitor_intervals=1, host_vm=(host, vm))
    assert host.cotenant("runner_burst") is None
    assert ([wl.name for wl in host.cotenants]
            == [spec.name for spec in plat.noise] + ["caller_disabled"])
    assert not sleeper.enabled              # caller state restored
    assert r.dispatches == vm.stat_passes - before_passes  # deltas only


def test_run_cachex_explicit_config_is_respected():
    """An explicitly passed ProbeConfig is authoritative; a seed argument
    overrides it only when actually given."""
    plat = get_platform(FAST_PLATFORM)
    cfg = ProbeConfig.for_platform(plat, seed=7, vev_target_sets=2)
    r = run_cachex(plat, monitor_intervals=1, config=cfg)
    assert r.vev_target_sets == 2           # config survived, not clobbered
    assert r.vev_built_sets == 2 and r.vev_success_rate == 1.0


def test_remove_cotenant():
    plat = get_platform(FAST_PLATFORM)
    host, _ = plat.make_host_vm(seed=3)
    wl = CotenantWorkload("tmp", 0, 10.0, polluter_gen())
    host.add_cotenant(wl)
    assert host.remove_cotenant("tmp") is wl
    assert host.cotenant("tmp") is None
    with pytest.raises(KeyError):
        host.remove_cotenant("tmp")


def test_deprecated_stage_shims_are_gone():
    """The PR-3 one-release DeprecationWarning shims are removed: importing
    them must fail, per docs/MIGRATION.md (stage drivers → session
    queries / plans)."""
    import repro.core.runner as runner
    for name in ("build_color_stage", "build_vscan_stage"):
        assert not hasattr(runner, name), name
        assert not hasattr(core, name), name
        assert name not in core.__all__
        with pytest.raises(ImportError):
            exec(f"from repro.core.runner import {name}")


def test_report_csv_is_generated_from_dataclass_fields():
    r = CacheXReport(
        platform="p", provisioning="dedicated", vev_target_sets=4,
        vev_built_sets=4, vev_verified_sets=4, vev_success_rate=1.0,
        detected_ways=8, n_colors=4, vcol_accuracy=1.0, vscan_sets=8,
        vscan_idle_rate=0.0, vscan_contended_rate=2.5,
        cas_tiers={0: 1, 1: 0}, cap_allocated=64, cap_rollovers=1,
        dispatches=404, accesses=123456, wall_s=1.25)
    header = CacheXReport.csv_header().split(",")
    assert header == [f.name for f in dataclasses.fields(CacheXReport)]
    cells = next(csv.reader(io.StringIO(r.csv_row())))
    assert len(cells) == len(header)
    row = dict(zip(header, cells))
    assert row["platform"] == "p" and row["detected_ways"] == "8"
    assert json.loads(row["cas_tiers"]) == {"0": 1, "1": 0}


# ---------------------------------------------------------------------------
# public-API snapshot
# ---------------------------------------------------------------------------

def _surface_lines():
    """Deterministic description of repro.core's exported surface: every
    __all__ name; for classes, dataclass fields and public methods."""
    lines = []
    for name in sorted(core.__all__):
        obj = getattr(core, name)
        if isinstance(obj, type):
            fields = ([f.name for f in dataclasses.fields(obj)]
                      if dataclasses.is_dataclass(obj) else [])
            methods = sorted(
                attr for attr, val in vars(obj).items()
                if not attr.startswith("_") and attr not in fields
                and (callable(val)
                     or isinstance(val, (property, classmethod,
                                         staticmethod))))
            desc = name
            if fields:
                desc += "(" + ", ".join(fields) + ")"
            if methods:
                desc += ": " + " ".join(methods)
            lines.append(desc)
        elif callable(obj):
            lines.append(f"{name}()")
        else:
            lines.append(f"{name} = {obj!r}")
    return lines


def test_public_api_snapshot():
    """Fails when the exported surface of repro.core changes without
    updating tests/data/core_api_snapshot.txt (regenerate with:
    PYTHONPATH=src:. python -c "from tests.test_abstraction import
    _surface_lines; print('\\n'.join(_surface_lines()))" > <snapshot>)."""
    with open(SNAPSHOT_PATH) as f:
        recorded = f.read().splitlines()
    current = _surface_lines()
    assert current == recorded, (
        "repro.core public surface changed; review the diff and update "
        f"{SNAPSHOT_PATH} if intentional")
