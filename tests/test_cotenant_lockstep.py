"""Multi-guest co-tenant windows (`host_model.wait_sharded`).

A lockstep ``Wait`` in `probeplan.execute_many` runs every guest's
co-tenant stream, each over the guest's own Wait length, as one committed
multi-guest call per (shard, padded length) group.  Each guest must end bit-identical to waiting alone through
`probeplan.execute`: machine state (tags, ages, clock, rng), host time,
event log and host rng.  A guest whose window a host event splits, and a
target that is no `GuestVM` on its own `SimHost`, wait alone.
"""

import jax
import numpy as np
import pytest

from repro.core import probeplan, trace
from repro.core.host_model import (CotenantWorkload, GuestVM, HostEvent,
                                   SimHost, polluter_gen, shard_slices)
from repro.core.probeplan import PlanLowering, ProbePlan, Wait
from repro.tpuprobe.pod_backend import SimPod
from tests.conftest import make_vm

WAIT_MS = 7.0
COUNTERS = ("cotenant_dispatches", "cotenant_accesses", "device_syncs",
            "cotenant_lockstep_streams")
# per guest: (kind, rate of its co-tenant in accesses a ms)
GUESTS = (
    ("quiet", 0.0),          # no co-tenant: dispatches nothing
    ("short", 30.0),         # 210 accesses: pads to 512
    ("long", 100.0),         # 700 accesses: pads to 1024
    ("l2_local", 30.0),      # a core-sharing tenant filling an L2
    ("remap", 30.0),         # a remap lands mid-window
    ("churn", 30.0),         # a co-tenant arrives mid-window
    ("long2", 120.0),        # 840: a second 1024 stream
)


def _guest(kind: str, rate: float, seed: int):
    host, vm = make_vm(seed=seed)
    if rate:
        host.add_cotenant(CotenantWorkload(
            "noise", 0, rate, polluter_gen(region_pages=512),
            core=1 if kind == "l2_local" else None,
            l2_local=kind == "l2_local"))
    if kind == "remap":
        host.schedule_event(HostEvent(at_ms=3.0, kind="remap",
                                      fraction=0.25))
    if kind == "churn":
        host.schedule_event(HostEvent(
            at_ms=2.5, kind="cotenant",
            add=CotenantWorkload("arrival", 0, 40.0,
                                 polluter_gen(region_pages=256))))
    return host, vm


def _guests(seed0=300):
    return [_guest(kind, rate, seed0 + i)
            for i, (kind, rate) in enumerate(GUESTS)]


# per guest, a Wait length of its own (the split guests keep WAIT_MS),
# as a VScan that shrank its own window sends: 20 ms of "short" pads to
# 1024, 3.5 ms of "long" to 512, 5 ms of "long2" to 1024
MIXED_MS = (3.0, 20.0, 3.5, WAIT_MS, WAIT_MS, WAIT_MS, 5.0)
# padded length of each guest's stream per window at (WAIT_MS, MIXED_MS);
# None for the split guests, which wait alone in the first window and
# draw 512-padded streams in the second (after its arrival the churn
# guest draws 7 * (30 + 40) = 490 accesses)
PADDED = {False: (0, 512, 1024, 512, None, None, 1024),
          True: (0, 1024, 512, 512, None, None, 1024)}


def _plans(n, shard_size, waits=None):
    hints = PlanLowering(shard_size=shard_size)
    waits = waits or (WAIT_MS,) * n
    # two windows: the split guests wait alone in the first, in lockstep
    # in the second
    return [ProbePlan(ops=(Wait(ms=ms), Wait(ms=ms)), hints=hints)
            for ms in waits]


def _counted(fn, *args):
    before = {k: trace.counter(k) for k in COUNTERS}
    fn(*args)
    return {k: trace.counter(k) - before[k] for k in COUNTERS}


def _assert_same_guest(a, b):
    (ha, va), (hb, vb) = a, b
    la, da = jax.tree_util.tree_flatten(ha.state)
    lb, db = jax.tree_util.tree_flatten(hb.state)
    assert da == db
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(x, y)
    assert ha.time_ms == hb.time_ms
    assert ([(e.kind, e.at_ms, e.applied_at_ms) for e in ha.event_log]
            == [(e.kind, e.at_ms, e.applied_at_ms) for e in hb.event_log])
    assert [w.name for w in ha.cotenants] == [w.name for w in hb.cotenants]
    assert va._timer_warm == vb._timer_warm
    assert ha.rng.integers(1 << 62) == hb.rng.integers(1 << 62)


@pytest.mark.parametrize("mixed", [False, True], ids=["same_ms", "mixed_ms"])
@pytest.mark.parametrize("shard_size", [None, 1, 2])
def test_lockstep_wait_bit_identical_to_per_guest(shard_size, mixed):
    many, alone = _guests(), _guests()
    n = len(many)
    waits = MIXED_MS if mixed else None
    for _, vm in many + alone:
        vm.warm_timer()            # the window must cool it
    got = _counted(probeplan.execute_many, [vm for _, vm in many],
                   _plans(n, shard_size, waits))
    want = {k: 0 for k in COUNTERS}
    for (_, vm), plan in zip(alone, _plans(n, shard_size, waits)):
        for k, v in _counted(probeplan.execute, vm, plan).items():
            want[k] += v
    for a, b in zip(many, alone):
        _assert_same_guest(a, b)

    # engine calls: the split guests' own calls in the first window, then
    # one per (shard, padded length) group of each window
    kinds = [kind for kind, _ in GUESTS]
    split = [kinds.index("remap"), kinds.index("churn")]
    first = dict(enumerate(PADDED[mixed]))
    second = {**first, split[0]: 512, split[1]: 512}
    split_calls = 0
    for i in split:
        h, vm = _guest(kinds[i], GUESTS[i][1], 300 + i)
        split_calls += _counted(vm.wait_ms, WAIT_MS)["cotenant_dispatches"]
    assert split_calls == 2 * len(split)   # each window split in two
    groups = 0
    for lengths in (first, second):
        for sl in shard_slices(n, shard_size):
            groups += len({lengths[i] for i in range(sl.start, sl.stop)
                           if lengths[i]})
    assert got["cotenant_dispatches"] == split_calls + groups
    assert got["cotenant_accesses"] == want["cotenant_accesses"]
    # only the split guests read their latencies back
    assert got["device_syncs"] == split_calls
    assert want["device_syncs"] == want["cotenant_dispatches"]
    # every guest with co-tenant traffic, except the split ones the first
    # time round
    assert got["cotenant_lockstep_streams"] == (n - 1 - 2) + (n - 1)
    assert want["cotenant_lockstep_streams"] == 0


def test_pod_targets_and_shared_hosts_wait_alone():
    """A pod slice in the group, and two guests on one host, take the
    per-target path; the other guests still run in lockstep."""
    pod, pod_alone = SimPod(seed=5), SimPod(seed=5)
    many, alone = _guests(400)[1:3], _guests(400)[1:3]
    shared = SimHost(many[0][0].geom, n_host_pages=1 << 14, seed=9)
    shared_alone = SimHost(many[0][0].geom, n_host_pages=1 << 14, seed=9)
    for h in (shared, shared_alone):
        h.add_cotenant(CotenantWorkload("noise", 0, 30.0,
                                        polluter_gen(region_pages=512)))
    twins = [GuestVM(shared, n_guest_pages=64, seed=s) for s in (1, 2)]
    twins_alone = [GuestVM(shared_alone, n_guest_pages=64, seed=s)
                   for s in (1, 2)]
    targets = [pod.slice()] + [vm for _, vm in many] + twins
    got = _counted(probeplan.execute_many, targets,
                   _plans(len(targets), None))
    for target, plan in zip([pod_alone.slice()] + [vm for _, vm in alone]
                            + twins_alone, _plans(len(targets), None)):
        probeplan.execute(target, plan)
    assert pod.time_ms == pod_alone.time_ms == 2 * WAIT_MS
    for a, b in zip(many + [(shared, twins[0])],
                    alone + [(shared_alone, twins_alone[0])]):
        _assert_same_guest(a, b)
    # the two guests of one host: four single-guest windows
    assert got["cotenant_lockstep_streams"] == 2 * 2
    assert got["cotenant_dispatches"] == 2 * 2 + 4
    assert got["device_syncs"] == 4

