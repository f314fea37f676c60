"""Traffic kind ``attach``: back-to-back ``CacheXSession.attach`` on
freshly booted hosts; a unit is one attach through the VSCAN build.

Parameters: ``hosts`` booted in set-up (more are booted in the window if
the window outlasts them), ``guest_pages`` per guest."""

import time
from typing import Dict

from benchmarks.chip import checks
from benchmarks.chip.generator import derive, warm

NUMBERS = ("window_error", "engine_mismatch", "abstraction_faults")


def drive(run, plat, traffic, rec, window) -> Dict:
    from repro.core import CacheXSession
    from repro.core.abstraction import ProbeConfig

    def boot(i):
        seed = derive(run.seed, "attach.host", i)
        _, vm = plat.make_host_vm(seed=seed,
                                  n_guest_pages=traffic["guest_pages"])
        cfg = ProbeConfig.for_platform(
            plat, seed=derive(run.seed, "attach.probe", i))
        return vm, cfg

    def one(i, vm, cfg):
        with run.span("attach"):
            s = CacheXSession.attach(vm, plat, cfg)
            with run.span("colors"):
                s.colors()
            with run.span("topology"):
                s.topology()
            with run.span("vscan"):
                s.monitored_sets()
        return s

    warm_vm, warm_cfg = boot("warmup")
    one("warmup", warm_vm, warm_cfg)
    warm(warm_vm.host.geom, traffic["warm_shapes"])
    hosts = [boot(i) for i in range(int(traffic["hosts"]))]
    sessions = []
    window.open()
    i = 0
    while window.is_open():
        vm, cfg = hosts[i] if i < len(hosts) else boot(i)
        t0 = time.perf_counter()
        s = one(i, vm, cfg)
        t1 = time.perf_counter()
        if t1 > run.t_close:
            break                     # finished after the window closed
        run.per_unit.setdefault("accesses", []).append(vm.stat_accesses)
        run.per_unit.setdefault("dispatches", []).append(vm.stat_passes)
        sessions.append(s)
        window.unit_done(t0, t1)
        i += 1
    return {"sessions": sessions}


def readings(rec, out, plat, control=False) -> Dict:
    r = {"window_error": int(out is None)}
    r.update(checks.engine_readings(rec, control))
    if out is None:
        r["abstraction_faults"] = None
        return r
    r.update(abstraction_faults=0, abstraction_checked=0,
             sessions=len(out["sessions"]))
    for s in out["sessions"]:
        for k, v in checks.abstraction_faults(s, plat).items():
            r[k] += v
    return r
