"""Device idle time by program span: one run of a cell with the program's
own tracer (``repro.core.trace``) switched on, for what the benchmark's
traced run does not yet read.

    python benchmarks/chip/program_spans.py --workload <cell> --seed <n> \
        --seconds <s> --mode traced|on

``traced``  the benchmark's traced run (``run.py --trace 1``: the profiler
            records the first ``trace_units`` units of the window), with
            program tracing reset and on for exactly those units.  The
            result line gains ``program``: idle seconds by innermost
            program span (``cachex:`` host events), idle time with no
            program span open, the longest idle gaps named
            ``<harness span>/<program span>``, and per traced unit of work
            (guest-intervals in a fleet, attaches) each span's count, total
            and self time and each counter's increase.
``on``      the untraced run (``run.py --trace 0``) with program tracing on
            from the start; against ``run.py --trace 0`` on the same seed,
            the cost of tracing.

Like ``run.py`` it refuses (exit 1) without a TPU, and (exit 2) without
the program or its tracer next to it.  The reduction (``attribute``) is
plain code over decoded planes, checked in ``tests/bench``.
"""

from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import shutil
import sys
import types
from typing import Dict, List, Optional, Tuple

if __package__ in (None, ""):
    _here = os.path.dirname(os.path.abspath(__file__))
    sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != _here]
    sys.path.insert(0, os.path.dirname(os.path.dirname(_here)))

from benchmarks.chip import devtrace, harness  # noqa: E402
from benchmarks.chip import run as bench  # noqa: E402

PROGRAM_PREFIX = "cachex:"


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

def decode(path: str):
    """As ``devtrace.decode``, keeping both the harness's ``bench:`` and
    the program's ``cachex:`` host events."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        device = bool(devtrace.DEVICE_PLANE_RE.match(plane.name))
        if not device and not plane.name.startswith("/host:"):
            continue
        lines = []
        for line in plane.lines:
            if device and line.name != devtrace.MODULE_LINE:
                continue
            lines.append((line.name, [
                (ev.name, ev.start_ns, ev.duration_ns) for ev in line.events
                if device or ev.name.startswith(
                    (devtrace.SPAN_PREFIX, PROGRAM_PREFIX))]))
        out.append((plane.name, lines))
    return out


def innermost(spans: List[Tuple[float, float, str]]
              ) -> List[Tuple[float, float, str]]:
    """Cut the timeline into pieces ``(start, end, name)``, each named by
    the innermost span open over it; time with no span open is in no
    piece.  Spans of one thread nest; a child reaching past its parent is
    cut at the parent's end."""
    pieces: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []       # (end, name)
    t = 0.0

    def close_until(x: float) -> None:
        nonlocal t
        while stack and stack[-1][0] <= x:
            end, name = stack.pop()
            if end > t:
                pieces.append((t, end, name))
                t = end

    for a, b, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        close_until(a)
        if stack:
            if a > t:
                pieces.append((t, a, stack[-1][1]))
            b = min(b, stack[-1][0])
        t = a
        stack.append((b, name))
    close_until(float("inf"))
    return pieces


def _at(pieces, starts, x: float) -> Optional[str]:
    i = bisect.bisect_right(starts, x) - 1
    if i >= 0 and pieces[i][0] <= x < pieces[i][1]:
        return pieces[i][2]
    return None


def attribute(planes, window: Optional[Tuple[float, float]] = None) -> Dict:
    """Idle device time by innermost program span.  ``idle_by_span`` maps a
    program span's name to the idle seconds during which it was the
    innermost one open; ``idle_unattributed_s`` is idle time with no
    program span open; ``idle_gaps`` are the longest gaps, named
    ``<harness span>/<program span>`` by their middle (the harness name
    alone where no program span is open there).  Seconds are averaged
    over the chips.  Returns {} when no device ran a program."""
    bench_spans, program = [], []
    first, last = float("inf"), float("-inf")
    devices = []
    for pname, lines in planes:
        for _, events in lines:
            for name, start, dur in events:
                first = min(first, start)
                last = max(last, start + dur)
                for prefix, into in ((devtrace.SPAN_PREFIX, bench_spans),
                                     (PROGRAM_PREFIX, program)):
                    if name.startswith(prefix):
                        into.append((start, start + dur, name[len(prefix):]))
        if devtrace.DEVICE_PLANE_RE.match(pname):
            devices.append([ev for lname, evs in lines
                            if lname == devtrace.MODULE_LINE for ev in evs])
    if not devices or not any(devices):
        return {}
    w0, w1 = window if window else (first, last)
    pieces = innermost(program)
    starts = [p[0] for p in pieces]
    idle = 0.0
    by_span: Dict[str, float] = {}
    gaps = []
    for mods in devices:
        busy = devtrace.merge([(max(s, w0), min(s + d, w1))
                               for _, s, d in mods
                               if min(s + d, w1) > max(s, w0)])
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        mine = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps += mine
        for a, b in mine:
            idle += b - a
            i = max(0, bisect.bisect_right(starts, a) - 1)
            while i < len(pieces) and pieces[i][0] < b:
                lo, hi = max(a, pieces[i][0]), min(b, pieces[i][1])
                if hi > lo:
                    name = pieces[i][2]
                    by_span[name] = by_span.get(name, 0.0) + (hi - lo)
                i += 1
    n = len(devices)
    attributed = sum(by_span.values())
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for g in gaps[:devtrace.TOP]:
        outer = devtrace.enclosing(bench_spans, g)
        inner = _at(pieces, starts, (g[0] + g[1]) / 2)
        named.append([f"{outer}/{inner}" if inner else outer,
                      (g[1] - g[0]) * 1e-9])
    return {
        "idle_s": idle * 1e-9 / n,
        "idle_by_span": {k: v * 1e-9 / n for k, v in sorted(
            by_span.items(), key=lambda kv: -kv[1])},
        "idle_unattributed_s": (idle - attributed) * 1e-9 / n,
        "idle_gaps": named,
    }


def per_unit(program: Dict, counters0: Dict[str, int], units: float
             ) -> Dict:
    """The program's record over the traced units, per unit: each span's
    count, total and self ms, and each counter's increase."""
    spans = {k: {"count": v["count"] / units,
                 "total_ms": 1e3 * v["total_s"] / units,
                 "self_ms": 1e3 * v["self_s"] / units}
             for k, v in sorted(program["spans"].items(),
                                key=lambda kv: -kv[1]["total_s"])}
    counters = {k: (v - counters0.get(k, 0)) / units
                for k, v in sorted(program["counters"].items())}
    staging = sum(v["self_ms"] for k, v in spans.items()
                  if k.startswith("stage:"))
    return {"spans": spans, "counters": counters,
            "staging_self_ms": staging, "dropped": program["dropped"]}


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def _profiled(jax, trace, rec: Dict):
    """The ``jax`` that ``run_cell`` is handed in a traced run: its
    profiler also resets and switches on the program's tracer with the
    capture, switches it off with it, and keeps a copy of the capture
    that ``run_cell`` deletes after reducing it."""

    def start_trace(trace_dir):
        jax.profiler.start_trace(trace_dir)
        trace.reset()
        trace.enable()
        rec["dir"] = trace_dir
        rec["counters0"] = trace.snapshot()["counters"]

    def stop_trace():
        trace.disable()
        rec["program"] = trace.snapshot()
        jax.profiler.stop_trace()
        paths = sorted(glob.glob(os.path.join(rec["dir"], "**",
                                              "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if paths:
            rec["copy"] = rec["dir"] + ".xplane.pb"
            shutil.copy(paths[-1], rec["copy"])

    profiler = types.SimpleNamespace(
        start_trace=start_trace, stop_trace=stop_trace,
        TraceAnnotation=jax.profiler.TraceAnnotation)
    return types.SimpleNamespace(profiler=profiler,
                                 local_devices=jax.local_devices)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--mode", choices=("traced", "on"), required=True)
    args = ap.parse_args(argv)
    ready = bench.prepare(args.workload)
    if isinstance(ready, int):
        return ready
    spec, cell, cfg, traffic, jax = ready
    try:
        from repro.core import trace
    except ImportError as e:
        print(f"bench: the program has no tracer ({e})", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    counters = harness.Counters(jax)
    trace_dir = os.path.join(os.getcwd(), ".bench_trace",
                             f"{cell['name']}-{args.seed}-program")
    traced = args.mode == "traced"
    rec: Dict = {}
    if not traced:
        trace.enable()
    try:
        res = bench.run_cell(cfg, traffic, args.seed, args.seconds, traced,
                             trace_dir,
                             counters, _profiled(jax, trace, rec)
                             if traced else jax)
    finally:
        trace.disable()
    line = bench.result_line(spec, cell, res, jax, traced)
    if traced and "copy" in rec:
        planes = decode(rec["copy"])
        os.remove(rec["copy"])
        run = res["run"]
        units = sum(u[2] for u in
                    run.units[: run.counters.get("traced_units", 0)])
        prog = attribute(planes, devtrace.window_of(planes))
        prog["per_unit"] = per_unit(rec["program"], rec["counters0"],
                                    max(units, 1))
        prog["units"] = units
        line["program"] = prog
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
