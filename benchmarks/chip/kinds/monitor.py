"""Traffic kind ``monitor``: one tenant's VSCAN monitoring loop,
back-to-back ``CacheXSession.refresh()`` on one attached guest while a
co-tenant pollutes the guest's own LLC domain; a unit is one refresh, one
guest-interval.

Parameters: ``guest_pages`` of the guest; ``polluter``, the co-tenant
(``domain``, ``rate_per_ms``, ``region_pages``) that joins once the guest
has attached on the quiet host; ``warmup_intervals`` refreshed in set-up.

The check follows the session from its first interval: the program's
single-guest executor (``probeplan.execute``) is wrapped to keep each
monitoring plan's lane order, window and measured lanes, and a subscriber
keeps each published view with the per-set rates the monitor kept and
the sets that were live.  The program's counters are kept at the end of
each unit; a counter the program does not have is left out, so its
metric reads nothing."""

import time
from typing import Dict

import numpy as np

from benchmarks.chip import checks
from benchmarks.chip import reference as ref
from benchmarks.chip.generator import derive, warm

NUMBERS = ("window_error", "engine_mismatch", "abstraction_faults",
           "rate_gap", "view_gap")
COUNTERS = ("probe_dispatches", "device_syncs", "cotenant_accesses")


def drive(run, plat, traffic, rec, window) -> Dict:
    from repro.core import CacheXSession, probeplan, trace
    from repro.core.abstraction import ProbeConfig
    from repro.core.platforms import NoiseSpec

    host, vm = plat.make_host_vm(seed=derive(run.seed, "monitor.host"),
                                 n_guest_pages=traffic["guest_pages"])
    cfg = ProbeConfig.for_platform(plat,
                                   seed=derive(run.seed, "monitor.probe"))
    session = CacheXSession.attach(vm, plat, cfg, eager=True)
    p = traffic["polluter"]
    host.add_cotenant(NoiseSpec(
        "polluter", domain=int(p["domain"]),
        rate_per_ms=float(p["rate_per_ms"]),
        region_pages=int(p["region_pages"])).workload())
    followed = follow(session)
    execute = probeplan.execute

    def executed(vm_, plan):
        result = execute(vm_, plan)
        if plan.label == "vscan.monitor":
            followed["monitor"].append((
                list(plan.meta["order"]), float(plan.meta["window_ms"]),
                [np.array(x) for x in result.last]))
        return result

    probeplan.execute = executed
    try:
        for _ in range(int(traffic["warmup_intervals"])):
            session.refresh()
        warm(host.geom, traffic["warm_shapes"])
        had = trace.snapshot()["counters"]
        kept = [k for k in COUNTERS if k in had]
        for k in kept:
            run.counters[k + "_at_open"] = had[k]
        window.open()
        while window.is_open():
            t0 = time.perf_counter()
            with run.span("refresh"):
                session.refresh()
            t1 = time.perf_counter()
            if t1 > run.t_close:
                break                 # finished after the window closed
            for k in kept:
                run.per_unit.setdefault(k, []).append(trace.counter(k))
            window.unit_done(t0, t1)
    finally:
        probeplan.execute = execute
    return {"session": session, "followed": followed}


def follow(session) -> Dict:
    """What the check needs of the session's monitoring from now on: the
    monitored sets as built, and every view it publishes with the per-set
    rates the monitor kept and the sets that were live."""
    mon = session.monitored_sets()
    vs = session._vs
    f = {"levels": [m.level for m in mon],
         "llc": np.array([m.level == "llc" for m in mon]),
         "domains": np.array([m.domain for m in mon]),
         "colors": np.array([m.color for m in mon]),
         "alpha": session.config.ewma_alpha, "monitor": [], "views": []}

    def on_view(view):
        f["views"].append((dict(view.per_domain), dict(view.per_color),
                           vs.history[-1].rate.copy(), ~vs.flagged))

    session.subscribe(on_view)
    return f


def monitor_gaps(f: Dict, control: bool = False) -> Dict:
    """``rate_gap`` and ``view_gap`` over every interval followed (with
    ``control``, the float32 reference's against the float64 one's)."""
    views = f["views"]
    if not views or len(f["monitor"]) != len(views):
        return {"rate_gap": None, "view_gap": None}
    want = [ref.set_rates(lanes, order, f["levels"], w)
            for order, w, lanes in f["monitor"]]
    live = [v[3] for v in views]
    shape = (f["llc"], f["domains"], f["colors"], f["alpha"])
    want_views = ref.ewma_views(want, live, *shape)
    out = {"rate_gap": max(checks._rel_gap(v[2], w)
                           for v, w in zip(views, want)),
           "view_gap": _views_gap([v[:2] for v in views], want_views),
           "intervals_checked": len(views)}
    if control:
        low = [ref.set_rates(lanes, order, f["levels"], w, np.float32)
               for order, w, lanes in f["monitor"]]
        out["control.rate_gap"] = max(checks._rel_gap(lo, w)
                                      for lo, w in zip(low, want))
        out["control.view_gap"] = _views_gap(
            ref.ewma_views(low, live, *shape, np.float32), want_views)
    return out


def _views_gap(got, want) -> float:
    return max(max(checks._dict_gap(d, wd), checks._dict_gap(c, wc))
               for (d, c), (wd, wc) in zip(got, want))


def readings(rec, out, plat, control=False) -> Dict:
    r = {"window_error": int(out is None)}
    r.update(checks.engine_readings(rec, control))
    if out is None:
        r.update(abstraction_faults=None, rate_gap=None, view_gap=None)
        return r
    r.update(checks.abstraction_faults(out["session"], plat))
    r.update(monitor_gaps(out["followed"], control))
    return r
