"""Runtime-layer tests: monitor, rebalancer, probes, serving, data, ckpt."""

import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tests._hypothesis_compat import given, settings, st

from repro.configs.base import ShapeSpec, get_config, reduced_config
from repro.data.pipeline import DataConfig, make_batch
from repro.distributed.rebalance import (StragglerMitigator,
                                         rebalanced_microbatches,
                                         replace_experts)
from repro.launch.mesh import make_host_mesh
from repro.tpuprobe.ici_probe import probe_axes, rank_axes_by_health
from repro.tpuprobe.monitor import PodMonitor, SimClock
from repro.tpuprobe.vmem_probe import (NOMINAL_VMEM, pick_attention_blocks,
                                       pick_ssd_block, probe_effective_vmem)


# -- monitor ----------------------------------------------------------------------

def test_monitor_detects_contention_and_tiers_commit():
    def schedule(device, t):
        return 3.0 if (device == 2 and t >= 2.0) else 1.0

    mon = PodMonitor(n_devices=4, clock=SimClock(schedule))
    for _ in range(2):
        mon.probe_once()
    assert mon.device_tiers() == {d: 0 for d in range(4)}
    for _ in range(6):
        mon.probe_once()
    tiers = mon.device_tiers()
    assert tiers[2] > 0
    assert all(tiers[d] == 0 for d in (0, 1, 3))
    assert mon.slow_devices() == [2]


def test_hbm_peak_is_keyed_by_device_kind():
    from repro.launch.mesh import hbm_peak_bytes_per_s
    assert hbm_peak_bytes_per_s("TPU v5 lite") == 819e9
    with pytest.raises(KeyError, match="cpu"):
        hbm_peak_bytes_per_s("cpu")


def test_monitor_probe_autoshrink():
    mon = PodMonitor(n_devices=2, clock=SimClock(lambda d, t: 4.0))
    d0 = mon.probe_bytes
    mon.probe_once()
    assert mon.probe_bytes < d0
    mon.clock.schedule = lambda d, t: 1.0
    mon.probe_once()
    assert mon.probe_bytes == d0


# -- rebalancer -----------------------------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(n=st.integers(2, 16), total=st.integers(16, 64),
       slow=st.floats(1.0, 6.0), seed=st.integers(0, 99))
def test_property_rebalance_preserves_total(n, total, slow, seed):
    rng = np.random.default_rng(seed)
    s = np.ones(n)
    s[rng.integers(n)] = slow
    plan = rebalanced_microbatches(s, total)
    assert plan.sum() == total
    assert plan.min() >= 1


def test_rebalance_sheds_work_from_straggler():
    s = np.array([1.0, 1.0, 1.0, 4.0])
    plan = rebalanced_microbatches(s, 32)
    assert plan[3] == plan.min()
    assert plan[3] < 8 < plan[:3].max() + 1


def test_mitigator_hysteresis_and_step_time():
    m = StragglerMitigator(n_devices=4, total_microbatches=32)
    uniform_t = m.step_time(np.array([1, 1, 1, 4.0]))
    slow = np.array([1, 1, 1, 4.0])
    m.update(slow); m.update(slow)
    assert m.rebalances == 0            # not yet committed
    m.update(slow)
    assert m.rebalances == 1
    rebal_t = m.step_time(slow)
    assert rebal_t < uniform_t          # straggler no longer gates the step


def test_expert_placement_hot_on_quiet():
    load = np.array([10.0, 1.0, 5.0, 1.0])     # expert 0 hottest
    tiers = {0: 2, 1: 0}                        # device 1 quiet
    pl = replace_experts(load, tiers, experts_per_device=2)
    assert pl.expert_to_device[0] == 1
    counts = np.bincount(pl.expert_to_device, minlength=2)
    assert (counts == 2).all()


# -- probes --------------------------------------------------------------------------

def test_ici_probe_ranks_degraded_axis():
    mesh = make_host_mesh()
    stats = probe_axes(mesh, link_model=lambda ax, h: 2.0
                       if ax == "data" else 1.0, n_floats=64)
    assert set(stats) == {"data", "model"}
    assert rank_axes_by_health(stats)[0] == "model"
    assert stats["data"]["slowdown"] > stats["model"]["slowdown"]


def test_vmem_probe_binary_search():
    for reserved in (2 << 20, 6 << 20):
        eff = probe_effective_vmem(reserved_model=reserved)
        true = NOMINAL_VMEM - reserved
        assert abs(eff - true) <= (1 << 18)


def test_tile_pickers_respect_budget():
    bq, bk = pick_attention_blocks(4 << 20, head_dim=128)
    ws = bq * 128 * 2 + 2 * bk * 128 * 2 + bq * 128 * 4 + bq * bk * 4 + \
        2 * bq * 4
    assert ws <= 0.7 * (4 << 20)
    big = pick_attention_blocks(14 << 20, head_dim=128)
    assert big[0] * big[1] >= bq * bk   # more budget -> same or bigger tiles
    assert pick_ssd_block(1 << 20, 64, 128, 128) >= 1


# -- data pipeline -----------------------------------------------------------------------

def test_data_determinism_and_resume():
    cfg = reduced_config(get_config("qwen1p5_0p5b"))
    shape = ShapeSpec("smoke", 64, 4, "train")
    d = DataConfig(seed=3)
    b1 = make_batch(d, cfg, shape, 17)
    b2 = make_batch(d, cfg, shape, 17)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = make_batch(d, cfg, shape, 18)
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    assert b1["tokens"].max() < cfg.vocab


def test_data_has_learnable_structure():
    cfg = reduced_config(get_config("qwen1p5_0p5b"))
    shape = ShapeSpec("smoke", 512, 2, "train")
    b = make_batch(DataConfig(seed=3), cfg, shape, 0)
    # motifs repeat across steps -> bigram entropy well below uniform
    toks = b["tokens"].ravel()
    _, counts = np.unique(toks, return_counts=True)
    p = counts / counts.sum()
    ent = -(p * np.log(p)).sum()
    assert ent < 0.8 * np.log(cfg.vocab)


# -- serving --------------------------------------------------------------------------------

def test_serve_engine_matches_manual_decode():
    from repro.models import lm
    from repro.serve.engine import Request, ServeEngine
    cfg = reduced_config(get_config("qwen1p5_0p5b"))
    params = lm.init_params(cfg, jax.random.PRNGKey(5))
    prompt = np.array([3, 1, 4, 1, 5], np.int32)

    eng = ServeEngine(cfg, params, batch_slots=2, max_len=32)
    eng.submit(Request(rid=0, prompt=prompt, max_new=4))
    eng.submit(Request(rid=1, prompt=prompt[:3], max_new=4))
    done = eng.run_until_drained()
    assert len(done) == 2 and all(len(r.out) == 4 for r in done)

    # manual single-sequence greedy reference for request 0
    caches = lm.init_caches(cfg, 1, 32)
    tok = jnp.asarray([[prompt[0]]], jnp.int32)
    outs = []
    step = jax.jit(lambda p, c, t, pos: lm.decode_step(cfg, p, c, t, pos))
    pos = 0
    cur = list(prompt)
    generated = 0
    while generated < 4:
        logits, caches = step(params, caches, tok, jnp.int32(pos))
        nxt = int(jnp.argmax(logits[0, -1]))
        pos += 1
        if pos >= len(prompt):
            outs.append(nxt)
            generated += 1
            cur.append(nxt)
        tok = jnp.asarray([[cur[pos]]], jnp.int32)
    r0 = [r for r in done if r.rid == 0][0]
    assert r0.out == outs


def test_replica_router_prefers_quiet_tier():
    from repro.core.cas import TierTracker
    from repro.serve.engine import ReplicaRouter
    tt = TierTracker(keys=[0, 1], thresholds=[1.2])
    for _ in range(3):
        tt.update({0: 9.0, 1: 0.5})
    r = ReplicaRouter(2, tiers=tt)
    assert [r.route() for _ in range(3)] == [1, 1, 1]


# -- elastic restore (different mesh) — subprocess owns its device count -----------

ELASTIC_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, numpy as np
    from repro.checkpoint import ckpt
    from repro.configs.base import get_config, reduced_config
    from repro.distributed.elastic import replan_batch, restore_on_mesh
    from repro.train import train_step as ts

    cfg = reduced_config(get_config("qwen1p5_0p5b"))
    hyper = ts.TrainHyper(microbatches=1, remat="none")
    mesh_a = jax.make_mesh((4, 2), ("data", "model"))
    mesh_b = jax.make_mesh((2, 4), ("data", "model"))
    with mesh_a:
        state = jax.jit(lambda k: ts.make_train_state(cfg, hyper, k),
                        out_shardings=ts.state_shardings(
                            cfg, mesh_a, ts.abstract_train_state(cfg, hyper))
                        )(jax.random.PRNGKey(0))
    ckpt.save("%s", 1, state)
    restored = restore_on_mesh("%s", 1, cfg, hyper, mesh_b)
    a = np.asarray(jax.device_get(state.params["head"]["unembed"]))
    b = np.asarray(jax.device_get(restored.params["head"]["unembed"]))
    np.testing.assert_array_equal(a, b)
    assert replan_batch(64, old_dp=4, new_dp=2, old_microbatches=2) == 4
    print("ELASTIC_OK")
""")


@pytest.mark.slow
def test_elastic_restore_across_meshes(tmp_path):
    script = ELASTIC_SCRIPT % (str(tmp_path), str(tmp_path))
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=600,
                       env={**__import__("os").environ,
                            "PYTHONPATH": "src"})
    assert "ELASTIC_OK" in r.stdout, r.stderr[-2000:]
