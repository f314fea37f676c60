"""System-side benchmarks: kernels, train step, serve step, roofline table."""

from __future__ import annotations

import glob
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timer


def bench_kernels():
    from repro.kernels.flash_attention import ops as fa_ops
    from repro.kernels.flash_attention import ref as fa_ref
    from repro.kernels.ssd_scan import ops as ssd_ops, ref as ssd_ref
    from repro.kernels.cachesim_step import ops as sim_ops
    from repro.kernels._compat import interpret_mode

    dev = jax.devices()[0]
    device = (f"device={dev.platform}:{dev.device_kind};"
              f"interpret={interpret_mode()}")
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    B, S, H, D = 1, 512, 4, 64
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.float32)
    fa_ops.flash_attention(q, k, v).block_until_ready()
    with timer() as t:
        for _ in range(3):
            fa_ops.flash_attention(q, k, v).block_until_ready()
    flops = 4 * B * S * S * H * D * 0.5
    emit("kernel.flash_attention", t["us"] / 3,
         f"S={S};flops={flops:.2e};{device}")

    b, S2, h, p, n = 1, 512, 8, 64, 64
    x = jax.random.normal(ks[3], (b, S2, h, p))
    dt = jax.random.normal(ks[4], (b, S2, h)) * .5
    A = -jnp.exp(jax.random.normal(ks[5], (h,)) * .3)
    Bm = jax.random.normal(ks[3], (b, S2, n)) * .3
    Cm = jax.random.normal(ks[4], (b, S2, n)) * .3
    Dm = jnp.ones((h,))
    ssd_ops.ssd_scan(x, dt, A, Bm, Cm, Dm, chunk=128)[0].block_until_ready()
    with timer() as t:
        for _ in range(3):
            ssd_ops.ssd_scan(x, dt, A, Bm, Cm, Dm,
                             chunk=128)[0].block_until_ready()
    emit("kernel.ssd_scan", t["us"] / 3, f"S={S2};chunk=128;{device}")

    rows, ways, T = 512, 8, 64
    tags = jnp.full((rows, ways), -1, jnp.int32)
    age = jnp.zeros((rows, ways), jnp.int32)
    streams = jnp.asarray(
        np.random.default_rng(0).integers(0, 4096, (rows, T)), jnp.int32)
    sim_ops.simulate_rows(tags, age, streams)[0].block_until_ready()
    with timer() as t:
        sim_ops.simulate_rows(tags, age, streams)[0].block_until_ready()
    emit("kernel.cachesim_rows", t["us"],
         f"rows={rows};T={T};accesses={rows*T};"
         f"per_access_ns={t['us']*1e3/(rows*T):.0f};{device}")


def bench_train_step():
    from repro.configs.base import ShapeSpec, get_config, reduced_config
    from repro.data.pipeline import DataConfig, make_batch
    from repro.launch.mesh import make_host_mesh
    from repro.train import train_step as ts

    cfg = reduced_config(get_config("qwen1p5_0p5b"))
    shape = ShapeSpec("bench", 128, 8, "train")
    mesh = make_host_mesh()
    hyper = ts.TrainHyper(microbatches=2, remat="none")
    batch = {k: jnp.asarray(v) for k, v in
             make_batch(DataConfig(), cfg, shape, 0).items()}
    with mesh:
        state = ts.make_train_state(cfg, hyper, jax.random.PRNGKey(0))
        step = jax.jit(ts.build_train_step(cfg, mesh, hyper),
                       donate_argnums=(0,))
        state, m = step(state, batch)
        jax.block_until_ready(m)
        with timer() as t:
            for _ in range(3):
                state, m = step(state, batch)
            jax.block_until_ready(m)
    toks = shape.global_batch * shape.seq_len
    emit("system.train_step_smoke", t["us"] / 3,
         f"tokens={toks};tok_per_s={toks/(t['s']/3):.0f}")


def bench_serve_step():
    from repro.configs.base import get_config, reduced_config
    from repro.models import lm
    cfg = reduced_config(get_config("qwen1p5_0p5b"))
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    caches = lm.init_caches(cfg, 8, 128)
    tok = jnp.zeros((8, 1), jnp.int32)
    step = jax.jit(lambda p, c, t, pos: lm.decode_step(cfg, p, c, t, pos))
    logits, caches = step(params, caches, tok, jnp.int32(0))
    jax.block_until_ready(logits)
    with timer() as t:
        for i in range(8):
            logits, caches = step(params, caches, tok, jnp.int32(i + 1))
        jax.block_until_ready(logits)
    emit("system.decode_step_smoke", t["us"] / 8,
         f"batch=8;tok_per_s={8/(t['s']/8):.0f}")


def bench_roofline_table():
    """Emit the §Roofline summary from the dry-run JSONs (one row/cell)."""
    cells = sorted(glob.glob("benchmarks/results/dryrun/*.json"))
    if not cells:
        emit("roofline.missing", 0.0, "run repro.launch.dryrun first")
        return
    worst = None
    for f in cells:
        d = json.load(open(f))
        if d["status"] != "ok":
            continue
        r = d["roofline"]
        frac = r["roofline_fraction"]
        emit(f"roofline.{d['arch']}.{d['shape']}.{d['mesh']}",
             r["step_lower_bound_s"] * 1e6,
             f"dom={r['dominant'][:-2]};frac={frac:.3f};"
             f"mem_gib={d['memory_analysis']['per_device_bytes']/2**30:.2f};"
             f"coll_gb={d['collectives'].get('tpu_corrected_bytes_per_device', d['collectives']['total_bytes_per_device'])/2**30:.1f}")
        if worst is None or frac < worst[1]:
            worst = (f, frac)
    if worst:
        emit("roofline.worst_cell", 0.0,
             f"{worst[0].split('/')[-1]};frac={worst[1]:.4f}")


def run_all():
    bench_kernels()
    bench_train_step()
    bench_serve_step()
    bench_roofline_table()
