"""What every cell of the chip benchmark shares.

* ``BENCHMARK.json`` lookups: a cell names a configuration, a traffic mix
  and its metrics; each is a file of its own under this directory, found
  by name (``configs/<config>.json``, ``traffic/<traffic>.json``,
  ``metrics/<metric>.py``), and a traffic mix names the kind of work that
  drives it (``kinds/<kind>.py``).  Adding a cell adds files and edits
  none.
* Turning a configuration file into the platform the program runs.
* The record of one run: harness spans, completed units of work, counters.
* XLA compile and persistent-cache counters, and device peak memory.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import re
import time
from typing import Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class BenchError(RuntimeError):
    """A cell, file or environment the benchmark cannot run."""


# ---------------------------------------------------------------------------
# BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------

def load_spec(root: str) -> Dict:
    path = os.path.join(root, "BENCHMARK.json")
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchError(f"no BENCHMARK.json in {root}")


def find_cell(spec: Dict, name: str) -> Dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise BenchError(f"unknown workload {name!r}; have "
                     f"{[c['name'] for c in spec['workloads']]}")


def config_path(name: str, base: str = HERE) -> str:
    return _existing(base, "configs", name, ".json")


def traffic_path(name: str, base: str = HERE) -> str:
    return _existing(base, "traffic", name, ".json")


def metric_path(name: str, base: str = HERE) -> str:
    return _existing(base, "metrics", name, ".py")


def kind_path(name: str, base: str = HERE) -> str:
    return _existing(base, "kinds", name, ".py")


def _existing(base: str, kind: str, name: str, ext: str) -> str:
    if not NAME_RE.match(name):
        raise BenchError(f"not a benchmark name: {name!r}")
    path = os.path.join(base, kind, name + ext)
    if not os.path.isfile(path):
        raise BenchError(f"missing benchmark file {path}")
    return path


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_metric(name: str, base: str = HERE) -> Callable:
    """The ``read(run)`` function of ``metrics/<name>.py``."""
    return _load(metric_path(name, base), "bench_metric_" + name).read


def load_kind(name: str, base: str = HERE):
    """The module of ``kinds/<name>.py``: ``drive(run, plat, traffic, rec,
    window)``, the compared numbers ``NUMBERS`` and ``readings(rec, out,
    plat, control)``."""
    return _load(kind_path(name, base), "bench_kind_" + name)


def _load(path: str, module_name: str):
    spec = importlib.util.spec_from_file_location(
        re.sub(r"\W", "_", module_name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(spec: Dict, cell: str, trace: bool) -> List[Dict]:
    """The metrics a run of ``cell`` reports: the end-to-end ones without
    the trace, the per-layer ones with it."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


# ---------------------------------------------------------------------------
# configuration file -> platform
# ---------------------------------------------------------------------------

def build_platform(cfg: Dict):
    """The platform a configuration file describes: the registry entry it
    names as its base, with every geometry and topology number taken from
    the file.  It is registered as ``bench.<name>``, apart from the
    registry's own entries, since the fleet's clones resolve their donor's
    platform by name; a second build of the same file in one process
    must describe the same platform."""
    from repro.core import get_platform
    from repro.core.cachesim import CacheGeometry
    from repro.core.platforms import list_platforms, register_platform
    base = get_platform(cfg["base_platform"])
    plat = dataclasses.replace(
        base, name="bench." + cfg["name"],
        l2=CacheGeometry(**cfg["l2"]), llc=CacheGeometry(**cfg["llc"]),
        llc_ways_total=cfg["llc_ways_total"],
        llc_slices_total=cfg["llc"]["n_slices"],
        n_domains=cfg["n_domains"],
        cores_per_domain=cfg["cores_per_domain"],
        replacement=cfg["replacement"], inclusion=cfg["inclusion"],
        provisioning=cfg["provisioning"])
    if plat.name not in list_platforms():
        return register_platform(plat)
    if get_platform(plat.name) != plat:
        raise BenchError(f"{plat.name} is registered with other numbers")
    return get_platform(plat.name)


# ---------------------------------------------------------------------------
# the record of one run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What one run measured.  ``units`` are the completed units of the
    window's work (a fleet interval round, an attach, a monitoring
    interval): (start, end, weight) on the host clock, ``weight`` being
    guest-intervals for a fleet round and 1 otherwise."""

    seed: int
    t_process: float
    t_window: float = 0.0
    t_close: float = 0.0
    units: List = dataclasses.field(default_factory=list)
    spans: Dict[str, List] = dataclasses.field(default_factory=dict)
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    per_unit: Dict[str, List[float]] = dataclasses.field(
        default_factory=dict)
    trace: Optional[Dict] = None
    tracing: bool = False

    @contextlib.contextmanager
    def span(self, name: str):
        """A harness span: kept on the host clock, and written into the
        profiler's trace as ``bench:<name>`` when the run is traced."""
        ann = contextlib.nullcontext()
        if self.tracing:
            import jax
            ann = jax.profiler.TraceAnnotation(f"bench:{name}")
        t0 = time.perf_counter()
        with ann:
            yield
        self.spans.setdefault(name, []).append((t0, time.perf_counter()))

    def in_window(self):
        return [u for u in self.units if u[1] <= self.t_close]

    @property
    def n_units(self) -> int:
        return len(self.in_window())

    @property
    def setup_s(self) -> float:
        return self.t_window - self.t_process


# ---------------------------------------------------------------------------
# compiles, cache hits, peak memory
# ---------------------------------------------------------------------------

class Counters:
    """XLA compiles and persistent-cache hits seen by this process."""

    def __init__(self, jax):
        self.compile_requests = 0
        self.cache_hits = 0

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_requests += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        # a backend compile request served from the persistent cache is
        # not a compile
        return self.compile_requests - self.cache_hits, self.cache_hits


def peak_bytes(jax) -> Dict[int, Optional[int]]:
    out = {}
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        out[d.id] = stats.get("peak_bytes_in_use")
    return out
