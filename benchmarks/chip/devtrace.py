"""Reduction of a JAX profiler trace to the benchmark's device numbers.

Reads the ``.xplane.pb`` a traced window wrote, through
``jax.profiler.ProfileData``:

* device busy time: the union of the intervals in which an XLA program
  ran on a TPU plane's ``XLA Modules`` line, averaged over the chips;
* device time per jitted program, by program name (``jit_<function>``),
  and the engines' share of it (the ``access_stream*`` programs);
* ``breakdown``: the programs that took most device time, and the
  longest idle gaps, each named by the innermost harness span
  (``bench:<name>`` host events) that enclosed it.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

ENGINE_RE = re.compile(r"access_stream")
DEVICE_PLANE_RE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
TOP = 10


def program_name(event_name: str) -> str:
    """``jit_access_stream(123)`` -> ``jit_access_stream``."""
    return re.sub(r"\(\d+\)$", "", event_name).strip()


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def reduce_planes(planes, window: Optional[Tuple[float, float]] = None
                  ) -> Dict:
    """Reduce decoded planes: ``planes`` is a list of (plane name, [(line
    name, [(event name, start_ns, duration_ns)])]).  ``window`` bounds
    the traced interval in the trace's own nanoseconds; by default it runs
    from the first to the last event of any plane.  Returns {} when no
    device ran a program."""
    devices = []
    spans = []
    first, last = float("inf"), float("-inf")
    for pname, lines in planes:
        for lname, events in lines:
            for name, start, dur in events:
                first = min(first, start)
                last = max(last, start + dur)
                if name.startswith(SPAN_PREFIX):
                    spans.append((start, start + dur,
                                  name[len(SPAN_PREFIX):]))
        if DEVICE_PLANE_RE.match(pname):
            mods = [ev for lname, evs in lines if lname == MODULE_LINE
                    for ev in evs]
            devices.append(mods)
    if not devices or not any(devices):
        return {}
    w0, w1 = window if window else (first, last)
    per_program: Dict[str, float] = {}
    busy = 0.0
    gaps = []
    for mods in devices:
        ivals = []
        for name, start, dur in mods:
            a, b = max(start, w0), min(start + dur, w1)
            if b <= a:
                continue
            ivals.append((a, b))
            key = program_name(name)
            per_program[key] = per_program.get(key, 0.0) + (b - a) * 1e-9
        merged = merge(ivals)
        busy += sum(b - a for a, b in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    n = len(devices)
    gaps.sort(key=lambda g: g[0] - g[1])
    top_gaps = [[enclosing(spans, g), (g[1] - g[0]) * 1e-9]
                for g in gaps[:TOP]]
    top_ops = sorted(per_program.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": busy / n,
        "window_s": (w1 - w0) * 1e-9,
        "per_program_s": {k: v / n for k, v in per_program.items()},
        "engine_s": sum(v for k, v in per_program.items()
                        if ENGINE_RE.search(k)) / n,
        "breakdown": {"device_ops": [[k, v / n] for k, v in top_ops],
                      "idle_gaps": top_gaps},
    }


def enclosing(spans, gap) -> str:
    """The innermost harness span around the middle of ``gap``."""
    mid = (gap[0] + gap[1]) / 2
    inside = [(b - a, name) for a, b, name in spans if a <= mid <= b]
    return min(inside)[1] if inside else "no span"


def decode(path: str):
    """The events the reduction reads: a device plane's program line, and
    the host's harness spans.  A TPU trace also holds an event per XLA op
    (scan steps included), which is most of its size and is skipped."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        device = bool(DEVICE_PLANE_RE.match(plane.name))
        if not device and not plane.name.startswith("/host:"):
            continue
        lines = []
        for line in plane.lines:
            if device and line.name != MODULE_LINE:
                continue
            lines.append((line.name, [
                (ev.name, ev.start_ns, ev.duration_ns) for ev in line.events
                if device or ev.name.startswith(SPAN_PREFIX)]))
        out.append((plane.name, lines))
    return out


def window_of(planes) -> Optional[Tuple[float, float]]:
    """The traced window: the outermost ``bench:window`` span, if any."""
    for _, lines in planes:
        for _, events in lines:
            for name, start, dur in events:
                if name == SPAN_PREFIX + "window":
                    return (start, start + dur)
    return None


def reduce_dir(trace_dir: str) -> Dict:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return {}
    planes = decode(paths[-1])
    return reduce_planes(planes, window_of(planes))


# -- what the per-layer metric files read -----------------------------------
# The trace covers the first ``trace_units`` units of the window (the
# traffic file's number), so per-unit device numbers divide by those.

def idle_percent(run) -> Optional[float]:
    t = run.trace
    if not t or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def engine_ms_per_unit(run, weighted: bool = False) -> Optional[float]:
    """Engine device ms per traced unit of work (per guest-interval when
    ``weighted``)."""
    t = run.trace
    units = run.units[: run.counters.get("traced_units", 0)]
    if not t or not units or not t.get("engine_s"):
        return None
    work = sum(u[2] for u in units) if weighted else len(units)
    return 1e3 * t["engine_s"] / work
