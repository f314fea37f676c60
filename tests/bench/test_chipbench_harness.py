"""The chip benchmark's harness: ``BENCHMARK.json`` against its contract,
file lookup by name, and the refusals that keep a CPU run or a checkout
without the program from printing a result."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmarks.chip import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SPEC = harness.load_spec(ROOT)
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
LINE_RE = re.compile(r"^[^\n\t]{1,200}$")


def _cells():
    return [c["name"] for c in SPEC["workloads"]]


def test_top_level_keys_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./\-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(ROOT, p))
    assert len(SPEC["command"]) <= 32
    for word in SPEC["command"]:
        assert LINE_RE.match(word)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    # the command names no repo file outside the benchmark's own paths
    files = [w for w in SPEC["command"] if os.path.exists(
        os.path.join(ROOT, w))]
    assert files and all(any(f.startswith(p + "/") for p in SPEC["paths"])
                         for f in files)


def test_names_and_units_use_only_allowed_characters():
    names = ([c["name"] for c in SPEC["configs"]] + _cells()
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert len(names) == len(set(names))
    for name in names:
        assert harness.NAME_RE.match(name), name
    for cell in SPEC["workloads"]:
        assert harness.NAME_RE.match(cell["config"])
        assert harness.NAME_RE.match(cell["traffic"])
        assert cell["chips"] in (1, 4)
        assert LINE_RE.match(cell["why"])
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    pairs = [(c["config"], c["traffic"]) for c in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    for cfg in SPEC["configs"]:
        assert set(cfg) == {"name", "source", "file", "reduced", "why"}
        assert LINE_RE.match(cfg["source"]) and LINE_RE.match(cfg["why"])
        assert len(cfg["reduced"]) <= 16
        for key in cfg["reduced"]:
            assert harness.NAME_RE.match(key)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert harness.UNIT_RE.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        for c in m.get("workloads", []):
            assert c in _cells()
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert LINE_RE.match(m["layer"])
        moved = [e for e in SPEC["end_to_end"] if e["name"] == m["moves"]]
        assert moved and moved[0]["name"] != "setup_s"


@pytest.mark.parametrize("cell", _cells())
def test_every_cell_resolves_its_files_by_name(cell):
    c = harness.find_cell(SPEC, cell)
    cfg = harness.load_json(harness.config_path(c["config"]))
    assert cfg["name"] == c["config"]
    entry = [x for x in SPEC["configs"] if x["name"] == c["config"]][0]
    assert os.path.samefile(os.path.join(ROOT, entry["file"]),
                            harness.config_path(c["config"]))
    traffic = harness.load_json(harness.traffic_path(c["traffic"]))
    assert callable(harness.load_kind(traffic["kind"]).drive)
    e2e = harness.cell_metrics(SPEC, cell, trace=False)
    layer = harness.cell_metrics(SPEC, cell, trace=True)
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
    assert layer
    for m in e2e + layer:
        assert callable(harness.load_metric(m["name"]))


def test_new_files_are_found_without_editing_any(tmp_path):
    for sub in ("configs", "traffic", "metrics", "kinds"):
        (tmp_path / sub).mkdir()
    (tmp_path / "configs" / "new_host.json").write_text('{"name": "x"}')
    (tmp_path / "traffic" / "new_mix.json").write_text('{"kind": "new_kind"}')
    (tmp_path / "kinds" / "new_kind.py").write_text(
        "NUMBERS = ('window_error',)\n"
        "def drive(run, plat, traffic, rec, window):\n    return {}\n")
    (tmp_path / "metrics" / "new.metric_s.py").write_text(
        "def read(run):\n    return 1.5\n")
    base = str(tmp_path)
    assert harness.load_json(harness.config_path("new_host", base)) == {
        "name": "x"}
    kind = harness.load_json(harness.traffic_path("new_mix", base))["kind"]
    assert harness.load_kind(kind, base).NUMBERS == ("window_error",)
    assert harness.load_metric("new.metric_s", base)(None) == 1.5
    with pytest.raises(harness.BenchError):
        harness.traffic_path("absent_mix", base)
    with pytest.raises(harness.BenchError):
        harness.config_path("../configs/new_host", base)


def _run_bench(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         _cells()[0], "--seed", "3000000001", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_a_cpu_backend_before_any_phase():
    out = _run_bench(ROOT)
    assert out.returncode == 1, out.stderr
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_command_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_bench(str(tmp_path))
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_configuration_files_keep_published_associativity():
    for entry in SPEC["configs"]:
        cfg = json.load(open(os.path.join(ROOT, entry["file"])))
        pub = cfg["published"]
        assert cfg["l2"]["n_ways"] == pub["l2"]["n_ways"]
        assert cfg["llc"]["n_ways"] == pub["llc"]["n_ways"]
        assert cfg["llc_ways_total"] == pub["llc"]["n_ways"]
        assert cfg["inclusion"] == pub["inclusion"]
        changed = {k for k in ("l2", "llc", "n_domains", "cores_per_domain")
                   if cfg[k] != pub[k]}
        assert changed == set(entry["reduced"]) == set(cfg["reduced"])
