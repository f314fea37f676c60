"""Chip benchmark of the CacheX probe stack.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout.  ``<cell>`` is a ``workloads`` entry of
``BENCHMARK.json``; its configuration, traffic mix, the kind of work
the mix names and its metrics are files under this directory, found by
name (``harness.py``).  The run refuses (exit 1, no result) unless JAX
finds a TPU with as many chips as the cell asks for, and (exit 2) when
the program is not next to it.

Set-up boots what the traffic needs and warms every engine shape it uses;
then the window measures for ``--seconds``.  With ``--trace 1`` the window
is traced by the JAX profiler and the per-layer metrics are reported in
place of the end-to-end ones.  After the window what the kind recorded
is held against the plain references (``checks.py``); the compared
numbers and their limits are the last lines on standard error and the
last key of the result, the JSON object on the last line of standard
output.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

if __package__ in (None, ""):
    # run as a script: import this directory as the package it is, from
    # the checkout's root, and not as loose top-level modules
    _here = os.path.dirname(os.path.abspath(__file__))
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != _here]
    sys.path.insert(0, os.path.dirname(os.path.dirname(_here)))

from benchmarks.chip import checks, devtrace, generator, harness  # noqa: E402
from benchmarks.chip.harness import BenchError  # noqa: E402


class Window:
    """The measured window: opened by the kind's driver once set-up is
    done.  In a traced run the profiler records its first ``trace_units``
    units."""

    def __init__(self, run, seconds, on_open, trace_units=0,
                 on_traced=None):
        self.run = run
        self.seconds = seconds
        self.on_open = on_open
        self.trace_units = trace_units
        self.on_traced = on_traced

    def open(self) -> None:
        self.on_open()
        self.run.spans.clear()
        self.run.t_window = time.perf_counter()
        self.run.t_close = self.run.t_window + self.seconds

    def is_open(self) -> bool:
        return time.perf_counter() < self.run.t_close

    def unit_done(self, start: float, end: float, weight: int = 1) -> None:
        self.run.units.append((start, end, weight))
        if self.on_traced and len(self.run.units) == self.trace_units:
            self.on_traced()
            self.on_traced = None


def run_cell(cfg: dict, traffic: dict, seed: int, seconds: float,
             tracing: bool, trace_dir: str, counters, jax,
             control: bool = False) -> dict:
    """Set up, measure and check one run of a cell; returns the run, the
    readings of the checks and the device numbers."""
    kind = generator.kind_of(traffic)
    plat = harness.build_platform(cfg)
    run = harness.Run(seed=seed, t_process=T_PROCESS, tracing=tracing)
    rec = checks.Recorder(generator.derive(seed, "sample"),
                          traffic["sample_skip"])
    rec.install()
    marks = {}

    def on_open():
        marks["compiles"] = counters.snapshot() if counters else (0, 0)
        if tracing:
            jax.profiler.start_trace(trace_dir)
            marks["span"] = jax.profiler.TraceAnnotation("bench:window")
            marks["span"].__enter__()
        rec.armed = True

    def stop_trace():
        marks.pop("span").__exit__(None, None, None)
        jax.profiler.stop_trace()
        run.counters["traced_units"] = len(run.units)

    window = Window(run, seconds, on_open, traffic["trace_units"],
                    stop_trace if tracing else None)
    try:
        try:
            out = kind.drive(run, plat, traffic, rec, window)
        except Exception:
            if not run.t_window:
                raise                 # set-up failed: no run to judge
            # the program raised under the timed path: the run is judged,
            # and is not correct
            traceback.print_exc()
            out = None
        finally:
            rec.armed = False
        if tracing and "span" in marks:
            stop_trace()              # the window ended first
        compiles = None
        if counters:
            c1, h1 = counters.snapshot()
            compiles = (c1 - marks["compiles"][0],
                        h1 - marks["compiles"][1])
        peaks = harness.peak_bytes(jax)
        if tracing:
            run.trace = devtrace.reduce_dir(trace_dir)
            shutil.rmtree(trace_dir, ignore_errors=True)
        readings = kind.readings(rec, out, plat, control)
    finally:
        rec.uninstall()
    return {"run": run, "readings": readings, "numbers": kind.NUMBERS,
            "compiles": compiles, "peaks": peaks}


def judge(res) -> dict:
    """``correct`` and the compared numbers of a run: every number within
    its limit, and at least one unit of work completed in the window."""
    verdict = checks.verdict(res["readings"], res["numbers"])
    verdict["correct"] = verdict["correct"] and res["run"].n_units > 0
    return verdict


def result_line(spec, cell, res, jax, tracing) -> dict:
    run = res["run"]
    metrics = {}
    for m in harness.cell_metrics(spec, cell["name"], tracing):
        value = harness.load_metric(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    devs = jax.local_devices()[: cell["chips"]]
    peaks = [v for k, v in res["peaks"].items()
             if v is not None and k in {d.id for d in devs}]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": max(peaks) if peaks else None}
    verdict = judge(res)
    line = {"correct": verdict["correct"], "attempted": run.n_units,
            "failed": 0, "metrics": metrics, "device": device}
    if tracing and run.trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        line["breakdown"] = run.trace["breakdown"]
    line["compared"] = verdict["compared"]
    return line


def prepare(workload: str):
    """Resolve a cell's files and find the program and the chip.  Returns
    (spec, cell, cfg, traffic, jax) or an exit code."""
    root = os.getcwd()
    try:
        spec = harness.load_spec(root)
        cell = harness.find_cell(spec, workload)
        cfg = harness.load_json(harness.config_path(cell["config"]))
        traffic = harness.load_json(harness.traffic_path(cell["traffic"]))
        harness.kind_path(traffic["kind"])
        for trace_on in (False, True):
            for m in harness.cell_metrics(spec, cell["name"], trace_on):
                harness.load_metric(m["name"])
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(root, "src"))
    try:
        import repro.core  # noqa: F401
    except ImportError as e:
        print(f"bench: the program is not in this checkout ({e})",
              file=sys.stderr)
        return 2
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"bench: needs {cell['chips']} TPU chip(s), found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 1
    return spec, cell, cfg, traffic, jax


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    ready = prepare(args.workload)
    if isinstance(ready, int):
        return ready
    spec, cell, cfg, traffic, jax = ready
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    counters = harness.Counters(jax)
    trace_dir = os.path.join(os.getcwd(), ".bench_trace",
                             f"{cell['name']}-{args.seed}")
    res = run_cell(cfg, traffic, args.seed, args.seconds,
                   bool(args.trace), trace_dir, counters, jax)
    run = res["run"]
    print(f"[bench] cell={cell['name']} seed={args.seed} "
          f"compile_cache={cache_dir} setup_s={run.setup_s} "
          f"units={run.n_units} window_compiles={res['compiles'][0]} "
          f"window_cache_hits={res['compiles'][1]} "
          f"peak_bytes_in_use={res['peaks']}", flush=True)
    print(f"[bench] readings {json.dumps(res['readings'])}", flush=True)
    line = result_line(spec, cell, res, jax, bool(args.trace))
    for name, c in line["compared"].items():
        print(f"[bench] compared {name}={c['value']} limit={c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
