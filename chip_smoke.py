"""Bring-up smoke run of the CacheX probe stack on a TPU.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the sharded fleet only

One process drives the chip(s); it starts no child processes.  Phases
run in order and the script exits non-zero at the first failure:

  device   fail unless ``jax.devices()[0].platform == "tpu"``
  attach   ``run_cachex`` through ``CacheXSession`` on all six
           platforms (sliced, CAT, two-domain, non-sliced,
           slice-partitioned, shared with a co-tenant):
           hypercall-verified VEV and VCOL at 100%, CAT detects 4/8 ways
  fleet    4-guest lockstep ``run_fleet_matrix`` on skylake_sp + milan_ccx:
           CAS steers the sensitive task to the quiet domain
  sharded  ``ShardedFleet`` of 64 guests on skylake_sp
  engine   ``access_stream``, ``access_streams_committed`` and
           ``access_streams_batched`` at the paper's Table 1 geometry,
           every latency bit-identical to the same call on the CPU device
  kernels  Pallas ``lru_sets``, ``prime_probe`` and ``triad`` compiled,
           checked against their ``ref.py`` on the CPU device, plus one
           ``measure_hbm_bandwidth`` call

With ``--chips 4`` only the cross-chip path runs: a 256-guest
``ShardedFleet`` dealt over four devices, compared report for report with
the same fleet run as one group (the script steers that one run by
patching the visible device count; the program has no such option).

Lines starting ``[bring-up]`` give each phase's wall time, XLA compiles,
persistent-cache hits and device ``peak_bytes_in_use``: they describe one
bring-up run and are not a benchmark.  The last line of standard output is
the JSON result ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from unittest import mock

HERE = os.path.dirname(os.path.abspath(__file__))

ATTACH_PLATFORMS = ("skylake_sp", "skylake_cat", "milan_ccx",
                    "icelake_sp", "skylake_slicepart", "skylake_shared")
FLEET_PLATFORMS = ["skylake_sp", "milan_ccx"]
WALL_FIELDS = ("wall_s", "guests_per_sec")
# engine phase sizes at Table 1 geometry (tests/test_chip_compile.py
# compiles the same programs for a described v5e chip)
ENGINE = dict(n_lanes=256, lane_len=64, n_guests=64, commit_len=512,
              warm_len=4096)
# kernel phase sizes: set rows, accesses per row, ways per kernel
KERNEL_ROWS, KERNEL_T, LRU_WAYS, PRIME_WAYS = 2048, 64, 16, 11
TRIAD_BYTES, HBM_PROBE_BYTES = 64 << 20, 256 << 20
# the four-chip fleet: 256 guests on a short loop (the device mapping is
# what is under test; the loop length only multiplies the wall)
FOUR_CHIP_GUESTS = 256
FOUR_CHIP_LOOP = dict(n_intervals=1, warmup=0, stream_len=64, ws_pages=4)


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


def peak_bytes(jax):
    out = {}
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        out[d.id] = stats.get("peak_bytes_in_use")
    return out


def run_phase(name, fn, jax, counters):
    c0, h0 = counters.snapshot()
    t0 = time.perf_counter()
    fn()
    wall = time.perf_counter() - t0
    c1, h1 = counters.snapshot()
    print(f"[bring-up] phase={name} wall_s={wall} compiles={c1 - c0} "
          f"cache_hits={h1 - h0} peak_bytes_in_use={peak_bytes(jax)}",
          flush=True)


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def report_diff(a, b):
    return [f.name for f in dataclasses.fields(a)
            if f.name not in WALL_FIELDS
            and getattr(a, f.name) != getattr(b, f.name)]


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_attach():
    from repro.core import get_platform, run_cachex
    for name in ATTACH_PLATFORMS:
        plat = get_platform(name)
        r = run_cachex(name, seed=17, monitor_intervals=2)
        print(f"[bring-up] attach {r.csv_row()}", flush=True)
        check(r.vev_success_rate == 1.0, f"{name}: VEV {r.vev_success_rate}")
        check(r.vcol_accuracy == 1.0, f"{name}: VCOL {r.vcol_accuracy}")
        if plat.provisioning == "cat":
            check((r.detected_ways, plat.llc_ways_total) == (4, 8),
                  f"{name}: CAT detected {r.detected_ways}/"
                  f"{plat.llc_ways_total} ways")


def phase_fleet():
    from repro.core.fleet import fig10_summary, run_fleet_matrix
    reports = run_fleet_matrix(platforms=FLEET_PLATFORMS)
    for r in reports:
        print(f"[bring-up] fleet {r.platform} {r.policy} cap={r.cap} "
              f"thr={r.throughput} quiet={r.quiet_residency} "
              f"ws_lat={r.ws_lat_cycles} dispatches={r.dispatches}",
              flush=True)
    f10 = fig10_summary(reports)
    check(f10["cas_quiet"] == f10["n_platforms"] == len(FLEET_PLATFORMS),
          f"CAS steered to the quiet domain on {f10['cas_quiet']}/"
          f"{f10['n_platforms']} platforms")


def phase_sharded(n_guests=64):
    from repro.core.fleet import ShardedFleet
    res = ShardedFleet("skylake_sp", n_guests).run()
    print(f"[bring-up] sharded n_guests={res.n_guests} "
          f"shard_size={res.shard_size} n_shards={res.n_shards} "
          f"n_devices={res.n_devices} boot_s={res.boot_s} run_s={res.run_s}",
          flush=True)
    check(len(res.reports) == n_guests and all(res.reports),
          "missing guest reports")
    quiet = sum(r.quiet_residency >= 0.5 for r in res.reports)
    check(quiet == n_guests, f"CAS quiet on {quiet}/{n_guests} guests")


def _engine_inputs(geom, seed):
    """Seeded Table-1 traffic: lanes of set-congruent blocks (so sets
    fill and evict) with -1 padding holes, mixed cores and co-tenants."""
    import numpy as np
    rng = np.random.default_rng(seed)
    n_sets = geom.llc.n_sets

    def congruent(shape):
        sets = rng.integers(0, n_sets, shape[:-1] + (1,))
        blocks = sets + n_sets * rng.integers(0, 64, shape)
        blocks[rng.random(shape) < 0.05] = -1
        return blocks.astype(np.int32)

    return rng, congruent


def phase_engine(n_lanes, lane_len, n_guests, commit_len, warm_len):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import cachesim as cs

    geom = cs.MachineGeometry(l2=cs.SKYLAKE_L2, llc=cs.skylake_llc(20))
    rng, congruent = _engine_inputs(geom, seed=0)
    warm = congruent((warm_len,))
    warm_cores = rng.integers(0, geom.n_cores, warm_len).astype(np.int32)
    warm_ct = rng.random(warm_len) < 0.3
    lanes = congruent((n_lanes, lane_len))
    lane_cores = rng.integers(0, geom.n_cores, n_lanes).astype(np.int32)
    lane_ct = rng.random(n_lanes) < 0.3
    commits = congruent((n_guests, commit_len))
    commit_cores = rng.integers(0, geom.n_cores,
                                (n_guests, commit_len)).astype(np.int32)
    commit_ct = rng.random((n_guests, commit_len)) < 0.3

    def run(dev):
        put = lambda x: jax.device_put(x, dev)
        state = jax.tree_util.tree_map(put, cs.init_machine(geom))
        state, warm_lats = cs.access_stream(state, geom, put(warm),
                                            put(warm_cores), put(warm_ct))
        batched = cs.access_streams_batched(
            state, geom, put(lanes), put(lane_cores), put(lane_ct),
            put(jnp.uint32(0)))
        stacked = cs.stack_states([state] * n_guests)
        stacked, commit_lats = cs.access_streams_committed(
            stacked, geom, put(commits), put(commit_cores), put(commit_ct))
        out = {"stream": warm_lats, "batched": batched,
               "committed": commit_lats,
               "committed_llc": stacked["llc"][0]}
        return {k: np.asarray(v) for k, v in out.items()}

    tpu = run(jax.devices()[0])
    cpu = run(jax.devices("cpu")[0])
    for k in tpu:
        check(tpu[k].shape == cpu[k].shape, f"engine {k}: shape")
        diff = int((tpu[k] != cpu[k]).sum())
        print(f"[bring-up] engine {k} shape={tpu[k].shape} "
              f"mismatches_vs_cpu={diff}", flush=True)
        check(diff == 0, f"engine {k}: {diff} entries differ from CPU")
    hits = {k: {int(v): int((tpu[k] == v).sum())
                for v in (cs.LAT_L2, cs.LAT_LLC, cs.LAT_DRAM)}
            for k in ("stream", "batched", "committed")}
    print(f"[bring-up] engine latency classes {hits}", flush=True)
    # non-vacuity: the traffic exercised every level
    check(all(min(h.values()) > 0 for h in hits.values()),
          "engine traffic missed a cache level")


def phase_kernels(rows=KERNEL_ROWS, T=KERNEL_T):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels._compat import interpret_mode
    from repro.kernels.cache_probe import ops as probe_ops, ref as probe_ref
    from repro.kernels.cachesim_step import ops as sim_ops, ref as sim_ref
    from repro.launch.mesh import hbm_peak_bytes_per_s

    check(not interpret_mode(), "kernels would run interpreted")
    cpu = jax.devices("cpu")[0]
    rng = np.random.default_rng(1)

    ways = LRU_WAYS
    tags = np.full((rows, ways), -1, np.int32)
    tags[:, : ways // 2] = rng.integers(0, 256, (rows, ways // 2))
    age = rng.integers(0, 8, (rows, ways)).astype(np.int32)
    streams = rng.integers(-1, 256, (rows, T)).astype(np.int32)
    got = sim_ops.simulate_rows(tags, age, streams)
    want = sim_ref.lru_sets_ref(*(jax.device_put(x, cpu)
                                  for x in (tags, age, streams)))
    for name, g, w in zip(("tags", "age", "hits"), got, want):
        check(np.array_equal(np.asarray(g), np.asarray(w)),
              f"lru_sets {name} differs from ref")
    print(f"[bring-up] kernel lru_sets rows={rows} ways={ways} T={T} "
          f"hits={int(np.asarray(got[2]).sum())} matches ref", flush=True)

    ways = PRIME_WAYS
    tags = np.full((rows, ways), -1, np.int32)
    tags[::2, :4] = rng.integers(1000, 1064, (rows // 2, 4))
    age = np.zeros((rows, ways), np.int32)
    streams = rng.integers(-1, 64, (rows, T)).astype(np.int32)
    targets = rng.integers(0, 64, rows).astype(np.int32)
    got = np.asarray(probe_ops.probe_verdicts(tags, age, streams, targets))
    want = np.asarray(probe_ref.prime_probe_ref(
        *(jax.device_put(x, cpu) for x in (tags, age, streams, targets))))
    check(np.array_equal(got, want), "prime_probe differs from ref")
    print(f"[bring-up] kernel prime_probe lanes={rows} ways={ways} T={T} "
          f"evicted={int(got.sum())} matches ref", flush=True)

    n = probe_ops.triad_rows(TRIAD_BYTES)
    a = (np.arange(n * 128) % 1000).astype(np.float32).reshape(n, 128)
    b = np.full((n, 128), 2.0, np.float32)
    s = np.asarray([3.0], np.float32)
    got = np.asarray(probe_ops.probe_triad(a, b, s))
    want = np.asarray(probe_ref.triad_ref(
        jax.device_put(a, cpu), jax.device_put(b, cpu), 3.0))
    check(np.array_equal(got, want), "triad differs from ref")
    print(f"[bring-up] kernel triad rows={n} matches ref", flush=True)

    bw, dt = probe_ops.measure_hbm_bandwidth(HBM_PROBE_BYTES, reps=3)
    kind = jax.devices()[0].device_kind
    check(bw > 0 and dt > 0, "measure_hbm_bandwidth")
    print(f"[bring-up] measure_hbm_bandwidth 256MiB bytes_per_s={bw} "
          f"seconds={dt} of_published_peak={bw / hbm_peak_bytes_per_s(kind)}",
          flush=True)


def phase_sharded_four(n_guests=FOUR_CHIP_GUESTS):
    import jax
    from repro.core import fleetshard
    from repro.core.fleet import ShardedFleet
    check(len(jax.local_devices()) == 4,
          f"--chips 4 needs 4 devices, found {len(jax.local_devices())}")
    spread = ShardedFleet("skylake_sp", n_guests, **FOUR_CHIP_LOOP).run()
    print(f"[bring-up] sharded4 spread n_devices={spread.n_devices} "
          f"n_shards={spread.n_shards} shard_size={spread.shard_size} "
          f"run_s={spread.run_s} peak_bytes_in_use={peak_bytes(jax)}",
          flush=True)
    check(spread.n_devices == 4, f"fleet used {spread.n_devices} devices")
    peaks = peak_bytes(jax)
    check(all(peaks.values()), f"a device held nothing: {peaks}")
    with mock.patch.object(fleetshard, "local_device_count", lambda: 1):
        single = ShardedFleet("skylake_sp", n_guests, **FOUR_CHIP_LOOP).run()
    print(f"[bring-up] sharded4 one_group n_devices={single.n_devices} "
          f"run_s={single.run_s}", flush=True)
    check(single.n_devices == 1, "comparison fleet was not one group")
    diffs = [(i, report_diff(a, b)) for i, (a, b) in
             enumerate(zip(spread.reports, single.reports))
             if report_diff(a, b)]
    print(f"[bring-up] sharded4 reports={len(spread.reports)} "
          f"differing={len(diffs)}", flush=True)
    check(len(spread.reports) == len(single.reports) == n_guests,
          "missing guest reports")
    check(not diffs, f"per-guest reports differ: {diffs[:4]}")


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    import jax

    dev = jax.devices()[0]
    count = len(jax.devices())
    print(f"[bring-up] device platform={dev.platform} "
          f"kind={dev.device_kind} count={count}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; refusing to run on "
              f"{dev.platform}", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    print(f"[bring-up] compile cache {cache_dir}", flush=True)
    counters = Counters(jax)

    if args.chips == 4:
        phases = [("sharded4", phase_sharded_four)]
    else:
        phases = [("attach", phase_attach), ("fleet", phase_fleet),
                  ("sharded", phase_sharded),
                  ("engine", lambda: phase_engine(**ENGINE)),
                  ("kernels", phase_kernels)]
    for name, fn in phases:
        run_phase(name, fn, jax, counters)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
