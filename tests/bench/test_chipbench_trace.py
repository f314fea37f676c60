"""The trace reduction: device busy and idle, device time per jitted
program, and the breakdown, on a small trace recorded on a TPU v5e and on
hand-made planes."""

import os

import pytest

from benchmarks.chip import devtrace

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "tpu_v5e_small.xplane.pb")


def _planes():
    dev = ("/device:TPU:0", [
        ("XLA Modules", [("jit_access_stream(11)", 100, 50),
                         ("jit_access_stream(11)", 160, 30),
                         ("jit_access_streams_batched(22)", 300, 100),
                         ("jit_convert_element_type(33)", 500, 10)]),
    ])
    host = ("/host:CPU", [
        ("python", [("bench:window", 0, 1000),
                    ("bench:refresh", 50, 500),
                    ("bench:guest_loop", 600, 300)]),
    ])
    return [dev, host]


def test_busy_programs_and_gaps_on_hand_made_planes():
    planes = _planes()
    r = devtrace.reduce_planes(planes, devtrace.window_of(planes))
    assert r["window_s"] == pytest.approx(1000e-9)
    # [100,150), [160,190), [300,400), [500,510)
    assert r["busy_s"] == pytest.approx(190e-9)
    assert r["engine_s"] == pytest.approx(180e-9)
    assert r["per_program_s"]["jit_access_stream"] == pytest.approx(80e-9)
    ops = r["breakdown"]["device_ops"]
    assert ops[0][0] == "jit_access_streams_batched"
    gaps = r["breakdown"]["idle_gaps"]
    # the longest gap [510, 1000) sits, at its middle, in guest_loop
    assert gaps[0] == ["guest_loop", pytest.approx(490e-9)]
    assert ["refresh", pytest.approx(110e-9)] in gaps
    assert len(gaps) <= devtrace.TOP


def test_no_device_program_reads_nothing():
    planes = [("/host:CPU", [("python", [("bench:window", 0, 10)])])]
    assert devtrace.reduce_planes(planes) == {}


def test_recorded_tpu_trace():
    """Recorded on one TPU v5e: five ``bench:refresh`` spans, each a VSCAN
    monitoring interval of skylake_sp_t1 (two ``access_stream`` programs
    and one ``access_streams_batched``).  Only the lines the reduction
    reads are kept: the device's program line and the harness spans."""
    planes = devtrace.decode(RECORDED)
    assert [p for p, _ in planes] == ["/device:TPU:0", "/host:CPU"]
    r = devtrace.reduce_planes(planes, devtrace.window_of(planes))
    assert r["busy_s"] == pytest.approx(0.136696741)
    assert r["window_s"] == pytest.approx(0.181117737)
    progs = r["per_program_s"]
    assert progs["jit_access_stream"] == pytest.approx(0.127501116)
    assert progs["jit_access_streams_batched"] == pytest.approx(0.009186736)
    assert r["engine_s"] == pytest.approx(
        progs["jit_access_stream"] + progs["jit_access_streams_batched"])
    assert r["engine_s"] <= r["busy_s"]
    gaps = r["breakdown"]["idle_gaps"]
    assert len(gaps) == devtrace.TOP
    assert {g[0] for g in gaps} == {"refresh"}
    assert r["breakdown"]["device_ops"][0][0] == "jit_access_stream"
