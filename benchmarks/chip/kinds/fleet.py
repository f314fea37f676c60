"""Traffic kind ``fleet``: a ``ShardedFleet`` of ``guests`` closed-loop
guests, run by the program's own ``ShardedFleet.run``; a unit is one
interval of every guest.

Parameters: ``guests``; ``fleet``, the keywords ``ShardedFleet`` and its
``FleetSim`` guests are built with (policy, placement thresholds, stream
and working-set sizes, and any scenario the program offers);
``warmup_intervals`` run in set-up; ``followed``, how many seeded guests
the check follows.

The fleet runs until it is stopped, which the benchmark does from here:
each guest's ``FleetSim.steps`` is wrapped so that every guest forwards
its plans unchanged, the window opens once the set-up intervals have
ended, each interval's end is reported to the window, and every guest
returns at the first probe point after the window has closed.  The first
guest decides, in each lockstep round, for all of them, so the fleet
stops in one round.  ``probeplan.execute_many``, the lockstep executor,
is wrapped for its span and for the check.
"""

import time
import types
from typing import Dict

import numpy as np

from benchmarks.chip import checks
from benchmarks.chip.generator import derive, dispatches, warm

NUMBERS = ("window_error", "engine_mismatch", "delivery_faults",
           "rate_gap", "view_gap", "placement_faults", "progress_gap")


def drive(run, plat, traffic, rec, window) -> Dict:
    from repro.core import fleet, probeplan

    n = int(traffic["guests"])
    kw = dict(traffic["fleet"])
    fl = fleet.ShardedFleet(plat, n, seed=derive(run.seed, "fleet.boot"),
                            n_intervals=10 ** 9, **kw)
    sims = fl.sims
    followed = np.random.default_rng(derive(run.seed, "fleet.followed")) \
        .choice(n, size=min(n, int(traffic["followed"])), replace=False)
    for i in sorted(followed.tolist()):
        rec.follow(i, sims[i], kw["thresholds"], kw["stream_len"])
    index = {id(s): i for i, s in enumerate(sims)}
    steps = fleet.FleetSim.steps
    execute_many = probeplan.execute_many
    state = {"stop": False, "warm_left": int(traffic["warmup_intervals"]),
             "t_start": None}
    ends = [0] * n                    # intervals each guest has ended

    def interval_done() -> None:
        now = time.perf_counter()
        if state["warm_left"] > 0:
            state["warm_left"] -= 1
            if state["warm_left"] == 0:
                warm(sims[0].host.geom, traffic["warm_shapes"])
                run.counters["dispatches_at_open"] = dispatches()
                window.open()
                state["t_start"] = run.t_window
            return
        run.per_unit.setdefault("dispatch_count", []).append(dispatches())
        window.unit_done(state["t_start"], now, n)
        state["t_start"] = now

    def stepped(sim):
        i = index[id(sim)]
        gen = steps(sim)
        plan = gen.send(None)
        while True:
            if i == 0:
                state["stop"] = bool(run.t_window) and not window.is_open()
            if state["stop"]:
                gen.close()
                return types.SimpleNamespace()   # stands for the report
            label = plan.label
            plan = gen.send((yield plan))
            if label == "vscan.monitor":
                rec.after_decision(i, sim)
            elif label == "fleet.ws_lat":
                ends[i] += 1
                if ends[i] > ends[-1] + 1:
                    raise RuntimeError("the fleet does not run its guests "
                                       "in lockstep")
                if i == n - 1:
                    interval_done()

    def executed(vms, plans):
        rec.before_round(plans)
        with run.span("execute_many"):
            results = execute_many(vms, plans)
        rec.after_round(vms, plans, results)
        return results

    fleet.FleetSim.steps = stepped
    probeplan.execute_many = executed
    try:
        res = fl.run()
    finally:
        fleet.FleetSim.steps = steps
        probeplan.execute_many = execute_many
    run.counters["shard_size"] = res.shard_size or n
    return {}


def readings(rec, out, plat, control=False) -> Dict:
    r = {"window_error": int(out is None)}
    r.update(checks.engine_readings(rec, control))
    r.update(checks.delivery_readings(rec, control))
    r.update(checks.monitor_readings(rec, control))
    r.update(checks.progress_readings(rec, control))
    return r
