"""How ``correct`` is decided: what the timed window produced, held against
the plain references in ``reference.py``.

While the window is open, :class:`Recorder` keeps a seeded sample of the
program's cache-engine calls (inputs copied on the device before the call,
outputs after) and of its fleet progress calls.  For the fleet it also
follows a seeded few guests from the start of the fleet's run: every
monitoring result the lockstep executor hands each of them, every view
each publishes, each placement and page-cache allocation each makes, and,
for a seeded few executor rounds, every multi-guest measurement call of
the round with each followed guest's machine state after it.  Once the
window has closed, each sample is replayed through the reference:

``engine_mismatch``  elements of the engines' latencies and committed
                     machine states that differ from the reference (an
                     exact comparison: the engine is integer).
``delivery_faults``  followed guests, in the captured rounds, whose
                     latencies differ from the reference's for the row of
                     the multi-guest call that ran on their own machine
                     state (or that no row of the round ran on).
``rate_gap``         widest relative gap of the per-set rates the monitor
                     kept from the reference's threshold count over the
                     latencies the guest was handed.
``view_gap``         widest relative gap of the published per-domain and
                     per-color rates from the reference's weighted means
                     of the reference's per-set rates.
``placement_faults`` placements that break the CAS rule over the tiers the
                     reference derives from the published views, and
                     page-cache allocations whose colors differ from the
                     CAP rule's over the same views.
``progress_gap``     widest relative gap of the fleet progress model's
                     outputs from the float32 reference.
``abstraction_faults``  eviction sets, monitored sets, page colors and
                     associativity that the host's page table refutes.
``window_error``     1 when the program raised inside the window.

Each number has its limit in ``LIMITS``; PERF.md gives the readings each
limit was set from.  With ``control=True`` the readings also give the
control's numbers (``control.<number>``): the reference put in the
program's place in the precision below the one the model states (16-bit
integers for the 32-bit engine, bfloat16 for the float32 progress model,
float32 for the float64 monitor), read on the same recorded inputs.
``control.py`` runs it; the benchmark's own runs do not.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np

from . import reference as ref

LIMITS = {
    "window_error": 0,
    "engine_mismatch": 0,
    "abstraction_faults": 0,
    "delivery_faults": 0,
    "placement_faults": 0,
    "progress_gap": 1e-5,
    "rate_gap": 1e-9,
    "view_gap": 1e-9,
}
ENGINES = ("access_stream", "access_streams_committed",
           "access_streams_batched", "access_streams_batched_multi")
RECORDS_PER_ENGINE = 3
PROGRESS_RECORDS = 8
CAPTURED_ROUNDS = 3
MEASURED = ("vscan.monitor", "fleet.ws_lat")


def machine_of(geom) -> ref.Machine:
    return ref.Machine(
        n_domains=geom.n_domains, cores_per_domain=geom.cores_per_domain,
        l2_sets=geom.l2.n_sets, l2_ways=geom.l2.n_ways,
        llc_sets=geom.llc.n_sets, llc_ways=geom.llc.n_ways,
        llc_slices=geom.llc.n_slices, replacement=geom.replacement,
        slice_seed=geom.slice_seed,
        inclusive=geom.inclusion == "inclusive")


def to_ref_state(state) -> Dict[str, np.ndarray]:
    return {"l2_tags": np.asarray(state["l2"][0]),
            "l2_age": np.asarray(state["l2"][1]),
            "llc_tags": np.asarray(state["llc"][0]),
            "llc_age": np.asarray(state["llc"][1]),
            "clock": np.asarray(state["clock"]),
            "rng": np.asarray(state["rng"])}


@dataclasses.dataclass
class Followed:
    """What one followed fleet guest was handed and did, interval by
    interval, from the start of the fleet's run."""

    vm: object
    levels: List[str]            # per monitored set
    llc: np.ndarray
    domains: np.ndarray
    colors: np.ndarray
    alpha: float
    vcpu_domain: Dict[int, int]
    free_counts: Dict[int, int]  # CAP's free pages per color
    monitor: List = dataclasses.field(default_factory=list)
    views: List = dataclasses.field(default_factory=list)
    decisions: List = dataclasses.field(default_factory=list)


class Recorder:
    """Seeded sample of the window's engine and progress calls, and the
    followed fleet guests.

    Installed by wrapping the program's public engine entries; the sample
    is taken only while the window is open.  The first call of each engine
    in the window is kept, then, after a seeded number of skipped calls
    (``mean_skip`` on average: the traffic file's ``sample_skip``), the
    next, up to ``RECORDS_PER_ENGINE`` each.  A kept call's input state is
    copied to the host before the call and its outputs after it."""

    def __init__(self, seed: int, mean_skip: int = 0):
        self.rng = np.random.default_rng(seed)
        self.mean_skip = int(mean_skip)
        self.armed = False
        self.calls: Dict[str, List] = {k: [] for k in ENGINES}
        self.skip: Dict[str, int] = {k: 0 for k in ENGINES}
        self.progress: List = []
        self.followed: Dict[int, Followed] = {}
        self.rounds: List[Dict] = []
        self.round_skip = 0
        self.capture = False
        self.thresholds: List[float] = []
        self.stream_pages = 0
        self._undo = []

    # -- installation -----------------------------------------------------
    def install(self) -> None:
        import jax
        from repro.core import cachesim, fleet

        # copies go to the host (no program is compiled for them); the
        # input copy is taken before the call, which may donate the state
        copy = lambda tree: jax.tree_util.tree_map(np.array, tree)
        for name in ENGINES:
            orig = getattr(cachesim, name)

            def wrapped(state, geom, *args, _orig=orig, _name=name):
                whole = self.capture and _name == ENGINES[3]
                keep = whole or self._take(_name)
                before = copy(state) if keep else None
                out = _orig(state, geom, *args)
                if whole:
                    self.rounds[-1]["calls"].append(
                        (geom, before, copy(args), copy(out)))
                elif keep:
                    self.calls[_name].append(
                        (geom, before, args, copy(out)))
                return out

            self._patch(cachesim, name, wrapped)

        orig_prog = fleet.fleet_interval_progress

        def progress(*args, _orig=orig_prog, **kw):
            out = _orig(*args, **kw)
            if (self.armed and len(self.progress) < PROGRESS_RECORDS
                    and self.rng.random() < 0.25):
                self.progress.append((args, kw, out))
            return out

        self._patch(fleet, "fleet_interval_progress", progress)

    def _patch(self, mod, name, fn) -> None:
        self._undo.append((mod, name, getattr(mod, name)))
        setattr(mod, name, fn)

    def uninstall(self) -> None:
        for mod, name, orig in reversed(self._undo):
            setattr(mod, name, orig)
        self._undo.clear()

    def _take(self, name: str) -> bool:
        if not self.armed or len(self.calls[name]) >= RECORDS_PER_ENGINE:
            return False
        if self.skip[name] > 0:
            self.skip[name] -= 1
            return False
        self.skip[name] = int(self.rng.integers(0, 2 * self.mean_skip + 1))
        return True

    # -- followed fleet guests ----------------------------------------------
    def follow(self, key: int, sim, thresholds, stream_len: int) -> None:
        """Follow fleet guest ``key`` (a ``FleetSim``) from now on: every
        view its session publishes, with the per-set rates the monitor
        kept and the sets that were live.  ``thresholds`` (CAS's tier
        bounds) and ``stream_len`` (pages CAP allocates an interval) are
        the traffic file's, as the fleet was given them."""
        self.thresholds = list(thresholds)
        self.stream_pages = int(stream_len)
        session = sim.session
        mon = session.monitored_sets()
        f = Followed(
            vm=sim.vm, levels=[m.level for m in mon],
            llc=np.array([m.level == "llc" for m in mon]),
            domains=np.array([m.domain for m in mon]),
            colors=np.array([m.color for m in mon]),
            alpha=session.config.ewma_alpha,
            vcpu_domain=dict(sim.vcpu_domain),
            free_counts={c: len(v) for c, v in sim.cap.free_lists.items()})
        vs = session._vs

        def on_view(view):
            f.views.append((dict(view.per_domain), dict(view.per_color),
                            vs.history[-1].rate.copy(), ~vs.flagged))

        session.subscribe(on_view)
        self.followed[key] = f

    def before_round(self, plans) -> None:
        """Called by the executor's wrapper before each lockstep round: a
        seeded few measured rounds in the window are captured whole."""
        if (not self.armed or plans[0].label not in MEASURED
                or len(self.rounds) >= CAPTURED_ROUNDS):
            return
        if self.round_skip > 0:
            self.round_skip -= 1
            return
        self.round_skip = int(self.rng.integers(0, 3))
        self.capture = True
        self.rounds.append({"calls": [], "guests": {}})

    def after_round(self, vms, plans, results) -> None:
        """Called with what the lockstep executor handed back."""
        import jax
        at = {id(vm): i for i, vm in enumerate(vms)}
        for key, f in self.followed.items():
            i = at.get(id(f.vm))
            if i is None or plans[i].label not in MEASURED:
                continue
            lanes = [np.array(x) for x in results[i].last]
            if plans[i].label == "vscan.monitor":
                f.monitor.append((list(plans[i].meta["order"]),
                                  float(plans[i].meta["window_ms"]), lanes))
            if self.capture:
                self.rounds[-1]["guests"][key] = (
                    jax.tree_util.tree_map(np.array, f.vm.host.state),
                    lanes)
        self.capture = False

    def after_decision(self, key: int, sim) -> None:
        """Called once guest ``key`` has placed its tasks and allocated its
        page-cache stream for the interval."""
        f = self.followed.get(key)
        if f is not None:
            f.decisions.append((
                [t.vcpu for t in sim.tasks],
                [sim.cap.page_color[p] for p in sim.cap.allocated_pages]))


# ---------------------------------------------------------------------------
# the comparisons
# ---------------------------------------------------------------------------

def _engine_outputs(name, m, state, args, dtype):
    """The reference's outputs for one recorded call, flattened to a list
    of arrays in the order the program returns them."""
    if name == "access_stream":
        blocks, cores, ct = (np.asarray(a) for a in args[:3])
        new, lats = ref.stream(m, to_ref_state(state), blocks, cores, ct,
                               dtype)
        return _state_list(new) + [lats]
    if name == "access_streams_committed":
        blocks, cores, ct = (np.asarray(a) for a in args[:3])
        new, lats = ref.committed(m, to_ref_state(state), blocks, cores, ct,
                                  dtype)
        return _state_list(new) + [lats]
    if name == "access_streams_batched":
        blocks, cores, ct = (np.asarray(a) for a in args[:3])
        salt = int(np.asarray(args[3])) if len(args) > 3 else 0
        return [ref.batched(m, to_ref_state(state), blocks, cores, ct, salt,
                            dtype)]
    blocks, cores, ct, salts = (np.asarray(a) for a in args[:4])
    return [ref.batched_multi(m, to_ref_state(state), blocks, cores, ct,
                              salts, dtype)]


def _state_list(s):
    return [s["l2_tags"], s["l2_age"], s["llc_tags"], s["llc_age"],
            s["clock"], s["rng"]]


def _program_outputs(name, out):
    if name in ("access_stream", "access_streams_committed"):
        state, lats = out
        return _state_list(to_ref_state(state)) + [np.asarray(lats)]
    return [np.asarray(out)]


def _mismatch(a_list, b_list) -> int:
    n = 0
    for a, b in zip(a_list, b_list):
        a, b = np.asarray(a), np.asarray(b)
        if a.shape != b.shape:
            n += max(a.size, b.size)
        else:
            n += int((a.astype(np.int64) != b.astype(np.int64)).sum())
    return n


def engine_readings(rec: Recorder, control: bool = False) -> Dict:
    """Mismatches of the program (and, with ``control``, of the 16-bit
    reference) against the 32-bit reference over the recorded calls."""
    out = {"engine_mismatch": 0, "engine_calls": 0}
    if control:
        out["control.engine_mismatch"] = 0
    for name, calls in rec.calls.items():
        for geom, before, args, res in calls:
            m = machine_of(geom)
            want = _engine_outputs(name, m, before, args, np.int32)
            out["engine_mismatch"] += _mismatch(
                _program_outputs(name, res), want)
            if control:
                low = _engine_outputs(name, m, before, args, np.int16)
                out["control.engine_mismatch"] += _mismatch(low, want)
            out["engine_calls"] += 1
    return out


def _rel_gap(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    scale = max(float(np.max(np.abs(b))), 1e-30) if b.size else 1.0
    return float(np.max(np.abs(a - b))) / scale if b.size else 0.0


def progress_readings(rec: Recorder, control: bool = False) -> Dict:
    import ml_dtypes
    prog = ctrl = 0.0
    for args, kw, res in rec.progress:
        args = [np.asarray(a) for a in args]
        want = ref.fleet_progress(*args, kw["n_domains"], kw["ticks"])
        for got, w in zip(res, want):
            prog = max(prog, _rel_gap(got, w))
        if control:
            low = ref.fleet_progress(*args, kw["n_domains"], kw["ticks"],
                                     dtype=ml_dtypes.bfloat16)
            for lo, w in zip(low, want):
                ctrl = max(ctrl, _rel_gap(lo, w))
    out = {"progress_gap": prog, "progress_calls": len(rec.progress)}
    if control:
        out["control.progress_gap"] = ctrl
    return out


def _same_state(a: Dict, b: Dict, row: int) -> bool:
    return all(np.array_equal(a[k], b[k][row]) for k in a)


def delivery_readings(rec: Recorder, control: bool = False) -> Dict:
    """Each followed guest of a captured round was handed the latencies of
    the row of the round's multi-guest calls that ran on its own machine
    state: its state after the round, which the measurement does not
    commit.  Compared with the reference's latencies for that row (with
    ``control``, the 16-bit reference's against the 32-bit one's)."""
    faults = checked = ctrl = 0
    for rnd in rec.rounds:
        for key, (state, lanes) in rnd["guests"].items():
            after = to_ref_state(state)
            want = low = None
            for geom, before, args, _ in rnd["calls"]:
                stacked = to_ref_state(before)
                rows = [r for r in range(len(stacked["clock"]))
                        if _same_state(after, stacked, r)]
                if not rows:
                    continue
                r = rows[0]
                one = {k: v[r:r + 1] for k, v in stacked.items()}
                blocks, cores, ct, salts = (np.asarray(a)[r:r + 1]
                                            for a in args[:4])
                m = machine_of(geom)
                want = (ref.batched_multi(m, one, blocks, cores, ct, salts),
                        blocks)
                if control:
                    low = ref.batched_multi(m, one, blocks, cores, ct, salts,
                                            np.int16)
                break
            checked += 1
            if want is None or not _handed(lanes, *want):
                faults += 1
            elif control and not _handed(lanes, low, want[1]):
                ctrl += 1
    out = {"delivery_faults": faults if checked else None,
           "delivery_checked": checked}
    if control:
        out["control.delivery_faults"] = ctrl
    return out


def _handed(lanes, lats, blocks) -> bool:
    """The guest's lanes are the row's real lanes, cut to their lengths."""
    real = int((blocks[0] >= 0).any(axis=1).sum())
    return len(lanes) == real and all(
        np.array_equal(np.asarray(x), lats[0, j, :len(x)])
        for j, x in enumerate(lanes))


def monitor_readings(rec: Recorder, control: bool = False) -> Dict:
    """``rate_gap``, ``view_gap`` and ``placement_faults`` of the followed
    guests, every interval from the start of the fleet's run."""
    rate = view = ctrl_rate = ctrl_view = 0.0
    placed = n = 0
    for f in rec.followed.values():
        if not f.views or not (len(f.monitor) == len(f.views)
                               == len(f.decisions)):
            return {"rate_gap": None, "view_gap": None,
                    "placement_faults": None}
        want = [ref.set_rates(lanes, order, f.levels, w)
                for order, w, lanes in f.monitor]
        for (_, _, kept, _), w in zip(f.views, want):
            rate = max(rate, _rel_gap(kept, w))
        live = [v[3] for v in f.views]
        views = ref.ewma_views(want, live, f.llc, f.domains, f.colors,
                               f.alpha)
        for (dom, col, _, _), (wd, wc) in zip(f.views, views):
            view = max(view, _dict_gap(dom, wd), _dict_gap(col, wc))
        if control:
            low = [ref.set_rates(lanes, order, f.levels, w, np.float32)
                   for order, w, lanes in f.monitor]
            for lo, w in zip(low, want):
                ctrl_rate = max(ctrl_rate, _rel_gap(lo, w))
            low_views = ref.ewma_views(low, live, f.llc, f.domains,
                                       f.colors, f.alpha, np.float32)
            for (ld, lc), (wd, wc) in zip(low_views, views):
                ctrl_view = max(ctrl_view, _dict_gap(ld, wd),
                                _dict_gap(lc, wc))
        tiers = ref.tiers([v[0] for v in f.views], rec.thresholds)
        colors = ref.cap_colors([v[1] for v in f.views], f.free_counts,
                                rec.stream_pages)
        for (vcpus, got), tier, want_colors in zip(f.decisions, tiers,
                                                   colors):
            placed += int(ref.cas_misplaced(vcpus, f.vcpu_domain, tier)
                          or got != want_colors)
            n += 1
    out = {"rate_gap": rate, "view_gap": view, "placement_faults": placed,
           "decisions": n, "views": sum(len(f.views)
                                        for f in rec.followed.values())}
    if control:
        out["control.rate_gap"] = ctrl_rate
        out["control.view_gap"] = ctrl_view
    return out


def _dict_gap(a: Dict, b: Dict) -> float:
    if sorted(a) != sorted(b):
        return float("inf")
    keys = sorted(b)
    return _rel_gap([a[k] for k in keys], [b[k] for k in keys])


def abstraction_faults(session, plat) -> Dict:
    """Check one attached session against the host's page table (the
    validation hypercall that exposes GPA -> HPA), with the reference's
    own set, slice and color arithmetic: the detected associativity, every
    LLC eviction set and monitored set (its lines share one set and slice,
    as many as the level has ways), and the color filters (each an L2
    eviction set, each of another true color).  Returns counts of checked
    items and of items the host refutes."""
    vm = session.vm
    ways = plat.llc.n_ways
    n_colors = max(1, plat.l2.n_sets // 64)

    def blocks_of(gvas) -> np.ndarray:
        return np.array([((vm.hypercall_hpa_page(int(g) >> 12) << 12)
                          | (int(g) & 0xFFF)) >> 6 for g in gvas], np.int64)

    def refuted(gvas, level: str) -> bool:
        blocks = blocks_of(gvas)
        if level == "l2":
            return (len(blocks) != plat.l2.n_ways
                    or len(set((blocks % plat.l2.n_sets).tolist())) != 1)
        slices = ref.slice_of(blocks, plat.llc.n_slices, plat.slice_seed)
        return (len(blocks) != ways or len(set(zip(
            (blocks % plat.llc.n_sets).tolist(), slices.tolist()))) != 1)

    topo = session.topology()
    items = [topo.detected_associativity != ways]
    items += [refuted(es.gvas, "llc") for es in session.llc_sets()]
    items += [refuted(m.es.gvas, m.level) for m in session.monitored_sets()]
    filters = session.colors().filters.filters
    items += [refuted(es.gvas, "l2") for es in filters]
    true_colors = [int(blocks_of(es.gvas[:1])[0] // 64) % n_colors
                   for es in filters]
    items.append(len(set(true_colors)) != len(filters))
    return {"abstraction_faults": int(sum(items)),
            "abstraction_checked": len(items)}


def verdict(readings: Dict, numbers: List[str]) -> Dict:
    """``correct`` and the compared numbers with their limits.  A number
    that could not be read (None, or not finite) fails and is given as
    None."""
    compared = {}
    for k in numbers:
        v = readings.get(k)
        if v is not None and not np.isfinite(v):
            v = None
        compared[k] = {"value": v, "limit": LIMITS[k]}
    ok = all(v["value"] is not None and v["value"] <= v["limit"]
             for v in compared.values())
    return {"correct": bool(ok), "compared": compared}
