"""Idle device time by program span (``benchmarks/chip/program_spans.py``):
the innermost-span timeline, the attribution of idle time, the
``<harness span>/<program span>`` gap names, and the reduction's agreement
with ``devtrace`` on a trace with no program spans."""

import os

import pytest

from benchmarks.chip import devtrace, program_spans

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "tpu_v5e_small.xplane.pb")


def _planes():
    dev = ("/device:TPU:0", [
        ("XLA Modules", [("jit_access_stream(11)", 100, 50),
                         ("jit_access_streams_batched_multi(22)", 300, 100),
                         ("jit_access_stream(11)", 500, 10)]),
    ])
    host = ("/host:CPU", [
        ("python", [("bench:window", 0, 1000),
                    ("bench:execute_many", 50, 600),
                    # plan [60, 640): Wait op [60, 300) with one
                    # co-tenant stream [70, 260) that syncs [150, 250)
                    ("cachex:plan:vscan.monitor", 60, 580),
                    ("cachex:op:Wait", 60, 240),
                    ("cachex:cotenant", 70, 190),
                    ("cachex:device:sync", 150, 100),
                    ("cachex:op:Measure", 300, 340),
                    # guest code after the round, then nothing open
                    ("cachex:fleet:decide", 700, 100)]),
    ])
    return [dev, host]


def test_innermost_pieces():
    spans = [(0, 10, "a"), (2, 4, "b"), (3, 4, "c"), (6, 12, "d")]
    assert program_spans.innermost(spans) == [
        (0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 6, "a"), (6, 10, "d")]
    assert program_spans.innermost([]) == []
    # time between two top-level spans is in no piece
    assert program_spans.innermost([(0, 1, "x"), (5, 6, "y")]) == [
        (0, 1, "x"), (5, 6, "y")]


def test_idle_by_program_span_and_unattributed():
    planes = _planes()
    r = program_spans.attribute(planes, devtrace.window_of(planes))
    # busy [100,150) [300,400) [500,510): idle [0,100) [150,300)
    # [400,500) [510,1000)
    assert r["idle_s"] == pytest.approx(840e-9)
    by = r["idle_by_span"]
    assert by["op:Wait"] == pytest.approx((70 - 60 + 300 - 260) * 1e-9)
    assert by["cotenant"] == pytest.approx((100 - 70 + 260 - 250) * 1e-9)
    assert by["device:sync"] == pytest.approx(100e-9)
    assert by["op:Measure"] == pytest.approx((500 - 400 + 640 - 510) * 1e-9)
    assert by["fleet:decide"] == pytest.approx(100e-9)
    assert "plan:vscan.monitor" not in by      # always inside an op here
    # no program span open: [0,60) and [640,700) and [800,1000)
    assert r["idle_unattributed_s"] == pytest.approx(320e-9)
    assert sum(by.values()) + r["idle_unattributed_s"] == \
        pytest.approx(r["idle_s"])


def test_gaps_named_by_harness_and_program_span():
    planes = _planes()
    r = program_spans.attribute(planes, devtrace.window_of(planes))
    gaps = dict((name, s) for name, s in r["idle_gaps"])
    # [510, 1000): middle 755 is in fleet:decide, outside execute_many
    assert gaps["window/fleet:decide"] == pytest.approx(490e-9)
    # [150, 300): middle 225 is in the co-tenant stream's sync
    assert gaps["execute_many/device:sync"] == pytest.approx(150e-9)
    # [400, 500): middle 450 in the Measure op
    assert gaps["execute_many/op:Measure"] == pytest.approx(100e-9)
    # [0, 100): middle 50 opens the harness span and no program span
    assert gaps["execute_many"] == pytest.approx(100e-9)


def test_no_device_program_reads_nothing():
    planes = [("/host:CPU", [("python", [("bench:window", 0, 10),
                                         ("cachex:cotenant", 1, 2)])])]
    assert program_spans.attribute(planes) == {}


def test_recorded_trace_without_program_spans_reads_as_devtrace():
    """The recorded trace predates the program's tracer: every idle second
    is unattributed and every gap keeps its harness name alone."""
    planes = program_spans.decode(RECORDED)
    window = devtrace.window_of(planes)
    r = program_spans.attribute(planes, window)
    d = devtrace.reduce_planes(devtrace.decode(RECORDED), window)
    assert r["idle_by_span"] == {}
    assert r["idle_s"] == pytest.approx(d["window_s"] - d["busy_s"])
    assert r["idle_unattributed_s"] == pytest.approx(r["idle_s"])
    assert r["idle_gaps"] == d["breakdown"]["idle_gaps"]


def test_per_unit_record():
    program = {"spans": {"stage:pad": {"count": 4, "total_s": 0.004,
                                       "self_s": 0.004},
                         "stage:noise": {"count": 2, "total_s": 0.002,
                                         "self_s": 0.002},
                         "op:Wait": {"count": 2, "total_s": 0.1,
                                     "self_s": 0.02}},
               "counters": {"cotenant_dispatches": 10, "device_syncs": 7},
               "dropped": 0}
    r = program_spans.per_unit(program, {"cotenant_dispatches": 2}, 2)
    assert list(r["spans"]) == ["op:Wait", "stage:pad", "stage:noise"]
    assert r["spans"]["op:Wait"]["total_ms"] == pytest.approx(50.0)
    assert r["counters"] == {"cotenant_dispatches": 4.0,
                             "device_syncs": 3.5}
    assert r["staging_self_ms"] == pytest.approx(3.0)
