"""The control of a cell, and the program's readings beside it.

    python benchmarks/chip/control.py --workload <cell> \
        --seeds 11,12,13 --seconds 10 [--in-place]

For each seed, in one process: set-up, a short window at the cell's own
load, then the checks of ``checks.py`` with ``control=True``, which read
both the program's numbers and the control's (the reference one precision
lower: 16-bit engine, bfloat16 progress model, float32 monitor) on the
same recorded calls.  With ``--in-place`` a second run per seed puts the
16-bit reference engine in the program's place for the window, so that
what the program builds on the engine's answers (the abstraction, the
views) is read under the control too.  Prints one JSON line per run;
PERF.md's limits were set from these readings.  The benchmark's own runs
(``run.py``) never run the control.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_here = os.path.dirname(os.path.abspath(__file__))
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != _here]
sys.path.insert(0, os.path.dirname(os.path.dirname(_here)))

import numpy as np  # noqa: E402

from benchmarks.chip import checks  # noqa: E402
from benchmarks.chip import reference as ref  # noqa: E402
from benchmarks.chip import run as bench  # noqa: E402


def _to_program(s, jnp):
    i32 = lambda a: jnp.asarray(np.asarray(a).astype(np.int32))
    return {"l2": (i32(s["l2_tags"]), i32(s["l2_age"])),
            "llc": (i32(s["llc_tags"]), i32(s["llc_age"])),
            "clock": i32(s["clock"]),
            "rng": jnp.asarray(np.asarray(s["rng"], np.uint32))}


def low_engines(jnp):
    """The four engines, computed by the reference in 16-bit integers,
    with the program's signatures."""
    low = np.int16

    def stream(state, geom, blocks, cores, ct):
        new, lats = ref.stream(checks.machine_of(geom),
                               checks.to_ref_state(state), blocks, cores,
                               ct, low)
        return _to_program(new, jnp), jnp.asarray(lats)

    def committed(states, geom, blocks, cores, ct):
        new, lats = ref.committed(checks.machine_of(geom),
                                  checks.to_ref_state(states), blocks,
                                  cores, ct, low)
        return _to_program(new, jnp), jnp.asarray(lats)

    def batched(state, geom, blocks, cores, ct, salt=0):
        return jnp.asarray(ref.batched(
            checks.machine_of(geom), checks.to_ref_state(state), blocks,
            cores, ct, int(np.asarray(salt)), low))

    def batched_multi(states, geom, blocks, cores, ct, salts):
        return jnp.asarray(ref.batched_multi(
            checks.machine_of(geom), checks.to_ref_state(states), blocks,
            cores, ct, salts, low))

    return {"access_stream": stream, "access_streams_committed": committed,
            "access_streams_batched": batched,
            "access_streams_batched_multi": batched_multi}


def in_place(jax):
    """Put the 16-bit engines in the program's place while the window is
    open; returns the undo."""
    from repro.core import cachesim
    lows = low_engines(jax.numpy)
    origs = {k: getattr(cachesim, k) for k in lows}
    live = {"on": False}
    for name, orig in origs.items():
        def call(*args, _o=orig, _l=lows[name]):
            return (_l if live["on"] else _o)(*args)
        setattr(cachesim, name, call)
    open_window = bench.Window.open

    def open_and_swap(self):
        open_window(self)
        live["on"] = True

    bench.Window.open = open_and_swap

    def undo():
        for name, orig in origs.items():
            setattr(cachesim, name, orig)
        bench.Window.open = open_window
    return undo


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--in-place", action="store_true")
    args = ap.parse_args(argv)
    ready = bench.prepare(args.workload)
    if isinstance(ready, int):
        return ready
    spec, cell, cfg, traffic, jax = ready
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        for placed in ((False, True) if args.in_place else (False,)):
            undo = in_place(jax) if placed else None
            try:
                res = bench.run_cell(cfg, traffic, seed,
                                     args.seconds, False, "", None, jax,
                                     control=True)
            finally:
                if undo:
                    undo()
            print(json.dumps({"cell": cell["name"], "seed": seed,
                              "in_place": placed,
                              "units": res["run"].n_units,
                              "correct": bench.judge(res)["correct"],
                              "readings": res["readings"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
