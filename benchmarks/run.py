"""Benchmark harness: one section per paper table/figure + system benches.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run --only fig10,roofline

Prints ``name,us_per_call,derived`` CSV rows (harness contract).  A
section that raises prints a ``<section>.ERROR`` row, the others still
run, and the process exits 1.

Trajectory artifacts: when ``$BENCH_PR`` is set (e.g. ``BENCH_PR=5``),
headline metrics each section `record()`s are flushed to
``benchmarks/BENCH_<pr>.json`` and appended to ``benchmarks/BENCH.csv``
as machine-written before/after rows — each metric's "before" is its most
recent "after" already in the CSV, so running the bench grows the
cross-PR trajectory without hand-editing.  Unset (the default for CI
smoke and ad-hoc runs) the tracked files stay untouched;
``--no-trajectory`` forces that even with ``$BENCH_PR`` set.
"""

from __future__ import annotations

import argparse
import csv as _csv
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def flush_trajectory(pr: str, sections_run, wall_s: float,
                     bench_dir: str = BENCH_DIR) -> None:
    """Write BENCH_<pr>.json and merge before/after rows into BENCH.csv.

    Rows are deduped on (pr, metric): re-running the same PR's bench
    *replaces* its rows in place (keeping their original "before", so the
    ``before = previous PR's after`` chain survives reruns) instead of
    appending duplicates.  A metric's "before" for a new row is the most
    recent "after" recorded by a *different* PR."""
    from benchmarks.common import TRAJECTORY
    payload = {"pr": pr, "sections": list(sections_run),
               "wall_s": round(wall_s, 1), "metrics": TRAJECTORY}
    json_path = os.path.join(bench_dir, f"BENCH_{pr}.json")
    with open(json_path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"# trajectory_json,{json_path},{len(TRAJECTORY)}", flush=True)
    if not TRAJECTORY:
        return
    csv_path = os.path.join(bench_dir, "BENCH.csv")
    rows = []
    header = ["pr", "metric", "before", "after", "notes"]
    if os.path.exists(csv_path):
        with open(csv_path) as f:
            r = _csv.reader(f)
            header = next(r, header)
            rows = [row + [""] * (5 - len(row)) for row in r if row]
    mine = {row[1]: row for row in rows if row[0] == pr}
    last = {}          # metric -> latest "after" from rows of OTHER PRs
    for row in rows:
        if row[0] != pr and len(row) > 3 and row[1]:
            last[row[1]] = row[3]
    replaced = appended = 0
    for m in TRAJECTORY:
        if m["metric"] in mine:    # rerun: replace in place, keep "before"
            old = mine[m["metric"]]
            old[3] = str(m["value"])
            old[4] = m["notes"]
            replaced += 1
        else:
            rows.append([pr, m["metric"], last.get(m["metric"], ""),
                         str(m["value"]), m["notes"]])
            appended += 1
    with open(csv_path, "w", newline="") as f:
        w = _csv.writer(f, lineterminator="\n")
        w.writerow(header)
        w.writerows(rows)
    print(f"# trajectory_csv,{csv_path},appended={appended},"
          f"replaced={replaced}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated name filters (substring match)")
    ap.add_argument("--no-trajectory", action="store_true",
                    help="skip writing BENCH_<pr>.json / BENCH.csv rows")
    args = ap.parse_args()
    filters = [f for f in args.only.split(",") if f]

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import bench_paper_tables, bench_system

    sections = [
        ("table2", bench_paper_tables.bench_table2_eviction_construction),
        ("table3", bench_paper_tables.bench_table3_associativity),
        ("table4", bench_paper_tables.bench_table4_color_lists),
        ("table5", bench_paper_tables.bench_table5_coverage),
        ("table6", bench_paper_tables.bench_table6_prime_probe),
        ("fig7b", bench_paper_tables.bench_fig7b_window_sensitivity),
        ("fig10", bench_paper_tables.bench_fig10_cas),
        ("fig11", bench_paper_tables.bench_fig11_cap),
        ("fig12", bench_paper_tables.bench_fig12_overhead),
        ("matrix", bench_paper_tables.bench_scenario_matrix),
        ("fleet", bench_paper_tables.bench_fleet),
        ("plans", bench_paper_tables.bench_plans),
        ("drift", bench_paper_tables.bench_drift),
        ("tune", bench_paper_tables.bench_tune),
        ("attack", bench_paper_tables.bench_attack),
        ("hierarchy", bench_paper_tables.bench_hierarchy),
        ("pod", bench_paper_tables.bench_pod),
        ("scale", bench_paper_tables.bench_scale),
        ("kernels", bench_system.bench_kernels),
        ("train", bench_system.bench_train_step),
        ("serve", bench_system.bench_serve_step),
        ("roofline", bench_system.bench_roofline_table),
    ]
    print("name,us_per_call,derived")
    t0 = time.time()
    ran = []
    failed = []
    for name, fn in sections:
        if filters and not any(f in name for f in filters):
            continue
        ran.append(name)
        try:
            fn()
        except Exception as e:  # keep the harness going; failures are rows
            failed.append(name)
            print(f"{name}.ERROR,0.0,{type(e).__name__}: {e}",
                  file=sys.stdout, flush=True)
    wall = time.time() - t0
    pr = os.environ.get("BENCH_PR")
    if pr and not args.no_trajectory:
        flush_trajectory(pr, ran, wall)
    print(f"# total_wall_s,{wall:.1f},", flush=True)
    if failed:
        sys.exit(f"benchmark sections failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
