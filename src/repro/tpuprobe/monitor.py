"""CacheX-TPU monitor: the paper's VSCAN loop over TPU-pod resources.

Probed resources (the vCache analogues — DESIGN.md §2):
  * per-chip effective HBM bandwidth  (cache_probe triad kernel),
  * per-axis ICI health               (ici_probe collective pings),
  * effective VMEM budget             (vmem_probe, one-shot).

Structure is the paper's, verbatim: periodic windowed probes between steps
(the idle-step analogue of pausing VM workloads), eviction-rate-style
normalization (here: *slowdown* = nominal/effective bandwidth), EWMA
smoothing, auto-shrinking probe size when the step budget is blown, and
qualitative tiers with 3-interval hysteresis feeding CAS-TPU
(`distributed/rebalance.py`) and CAP-TPU (`vmem_probe.pick_*` +
`data/pipeline.ColoredStagingPool`).

Clock injection: on real TPUs `clock=None` times the actual kernels on
each local device and normalizes by that chip's published HBM peak
(`launch.mesh.hbm_peak_bytes_per_s`, keyed by `device_kind`); CPU tests
and examples inject a `SimClock` whose contention schedule plays back
interference — the full control path (probe -> EWMA -> tier ->
rebalance) is exercised identically.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from repro.core.cas import TierTracker
from repro.kernels.cache_probe.ops import measure_hbm_bandwidth
from repro.launch.mesh import HBM_BW, hbm_peak_bytes_per_s


@dataclasses.dataclass
class ProbeSample:
    device: int
    effective_bw: float      # bytes/s
    slowdown: float          # nominal / effective  (>= 1.0 under contention)
    t: float


class SimClock:
    """Deterministic contention playback for CPU-only validation.

    `schedule(device, t)` -> slowdown factor; the monitor's probe timing is
    synthesized as nominal_time * slowdown.
    """

    def __init__(self, schedule: Callable[[int, float], float]):
        self.schedule = schedule
        self.t = 0.0

    def advance(self, dt: float) -> None:
        self.t += dt

    def probe_time(self, device: int, nominal_s: float) -> float:
        return nominal_s * float(self.schedule(device, self.t))


class PodMonitor:
    """Periodic per-device contention monitor + tier tracker."""

    def __init__(self, n_devices: int, clock: Optional[SimClock] = None,
                 probe_bytes: int = 64 * (1 << 20),
                 ewma_alpha: float = 0.3,
                 tier_thresholds=(1.15, 1.5),
                 interval_s: float = 1.0):
        self.n_devices = n_devices
        self.clock = clock
        self.probe_bytes = probe_bytes
        self.default_probe_bytes = probe_bytes
        self.ewma_alpha = ewma_alpha
        self.interval_s = interval_s
        self.ewma = np.ones(n_devices)          # slowdown EWMA
        self.tiers = TierTracker(keys=list(range(n_devices)),
                                 thresholds=list(tier_thresholds))
        self.history: List[List[ProbeSample]] = []

    # -- one monitoring interval ------------------------------------------------
    def probe_once(self) -> List[ProbeSample]:
        nominal_s = self.probe_bytes / HBM_BW
        samples = []
        for d in range(self.n_devices):
            if self.clock is not None:
                dt = self.clock.probe_time(d, nominal_s)
                eff = self.probe_bytes / max(dt, 1e-12)
                slow = max(1.0, dt / nominal_s)
                t = self.clock.t
            else:  # real hardware: time the actual triad kernel on chip d
                dev = jax.local_devices()[d]
                with jax.default_device(dev):
                    eff, _ = measure_hbm_bandwidth(self.probe_bytes, reps=1)
                slow = max(1.0, hbm_peak_bytes_per_s(dev.device_kind) / eff)
                t = time.time()
            samples.append(ProbeSample(device=d, effective_bw=eff,
                                       slowdown=slow, t=t))
        slows = np.array([s.slowdown for s in samples])
        self.ewma = (1 - self.ewma_alpha) * self.ewma + self.ewma_alpha * slows
        self.tiers.update({d: float(self.ewma[d])
                           for d in range(self.n_devices)})
        # auto-shrink (paper §3.3): if the probe budget is blown everywhere,
        # halve the probe size; restore when quiet
        if float(slows.min()) > 2.0:
            self.probe_bytes = max(self.probe_bytes // 2, 1 << 20)
        elif float(slows.max()) < 1.05:
            self.probe_bytes = self.default_probe_bytes
        self.history.append(samples)
        if self.clock is not None:
            self.clock.advance(self.interval_s)
        return samples

    # -- consumers ------------------------------------------------------------
    def device_tiers(self) -> Dict[int, int]:
        return dict(self.tiers.tier)

    def slow_devices(self, tier_at_least: int = 1) -> List[int]:
        return [d for d, t in self.tiers.tier.items() if t >= tier_at_least]

    def per_device_slowdown(self) -> np.ndarray:
        return self.ewma.copy()
