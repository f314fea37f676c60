"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tests._hypothesis_compat import given, settings, st

from repro.kernels import _compat
from repro.kernels.cache_probe import ops as probe_ops, ref as probe_ref
from repro.kernels.cache_probe.kernel import TRIAD_BLOCK, triad
from repro.kernels.cachesim_step import ops as sim_ops, ref as sim_ref
from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.ssd_scan import ops as ssd_ops, ref as ssd_ref

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


# -- flash attention ------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,Hq,Hkv,D,causal", [
    (1, 128, 2, 2, 64, True),
    (2, 256, 4, 2, 64, True),
    (1, 256, 4, 1, 128, True),      # strong GQA grouping
    (2, 128, 2, 2, 128, False),     # bidirectional (encoder)
    (1, 384, 6, 2, 64, True),       # non-power-of-two heads
])
def test_flash_attention_sweep(B, S, Hq, Hkv, D, causal, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
    out = fa_ops.flash_attention(q, k, v, causal=causal)
    exp = fa_ref.attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **TOL[dtype])


def test_flash_attention_matches_model_chunked_path():
    """The Pallas kernel and the model's chunked-scan path must agree (they
    are the two selectable `impl` backends)."""
    from repro.models.attention import chunked_attention
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    B, S, H, D = 2, 256, 4, 64
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.float32)
    a = fa_ops.flash_attention(q, k, v, causal=True)
    b = chunked_attention(q, k, v, causal=True, chunk_q=128, chunk_k=128)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)


# -- ssd scan ----------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,S,h,p,n,chunk", [
    (1, 128, 4, 32, 16, 32),
    (2, 256, 8, 64, 32, 64),
    (1, 256, 8, 64, 128, 128),   # mamba2-2.7b-like state width
    (2, 64, 2, 32, 16, 64),      # single chunk
])
def test_ssd_scan_sweep(b, S, h, p, n, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    x = jax.random.normal(ks[0], (b, S, h, p), dtype)
    dt = (jax.random.normal(ks[1], (b, S, h), jnp.float32) * 0.5).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = (jax.random.normal(ks[3], (b, S, n)) * 0.3).astype(dtype)
    C = (jax.random.normal(ks[4], (b, S, n)) * 0.3).astype(dtype)
    D = jax.random.normal(ks[5], (h,))
    y_k, st_k = ssd_ops.ssd_scan(x, dt, A, B, C, D, chunk=chunk)
    y_r, st_r = ssd_ref.ssd_chunked_ref(x, dt, A, B, C, D, chunk)
    np.testing.assert_allclose(np.asarray(y_k, np.float32),
                               np.asarray(y_r, np.float32), **TOL[dtype])
    np.testing.assert_allclose(np.asarray(st_k), np.asarray(st_r),
                               rtol=1e-2 if dtype == jnp.bfloat16 else 2e-5,
                               atol=1e-2 if dtype == jnp.bfloat16 else 2e-5)


def test_ssd_state_equals_stepwise_decode():
    """Chunked-scan final state == sequential O(1) decode recurrence."""
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    b, S, h, p, n = 1, 64, 2, 16, 8
    x = jax.random.normal(ks[0], (b, S, h, p))
    dt = jax.random.normal(ks[1], (b, S, h)) * 0.5
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, S, n)) * 0.3
    C = jax.random.normal(ks[4], (b, S, n)) * 0.3
    D = jnp.zeros((h,))
    _, st = ssd_ref.ssd_chunked_ref(x, dt, A, B, C, D, chunk=16)
    # stepwise recurrence
    dtv = jax.nn.softplus(dt)
    st2 = jnp.zeros((b, h, p, n))
    for t in range(S):
        dec = jnp.exp(dtv[:, t] * A[None])
        st2 = st2 * dec[..., None, None] + jnp.einsum(
            "bh,bhp,bn->bhpn", dtv[:, t], x[:, t], B[:, t])
    np.testing.assert_allclose(np.asarray(st), np.asarray(st2), rtol=1e-4,
                               atol=1e-4)


# -- cachesim step --------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(rows=st.sampled_from([4, 8, 16]), ways=st.sampled_from([4, 8]),
       T=st.integers(1, 48), seed=st.integers(0, 99))
def test_property_lru_kernel_matches_ref(rows, ways, T, seed):
    rng = np.random.default_rng(seed)
    tags = np.full((rows, ways), -1, np.int32)
    age = np.zeros((rows, ways), np.int32)
    streams = rng.integers(-1, 32, size=(rows, T)).astype(np.int32)
    t_k, a_k, h_k = sim_ops.simulate_rows(jnp.asarray(tags),
                                          jnp.asarray(age),
                                          jnp.asarray(streams))
    t_r, a_r, h_r = sim_ref.lru_sets_ref(jnp.asarray(tags),
                                         jnp.asarray(age),
                                         jnp.asarray(streams))
    np.testing.assert_array_equal(np.asarray(t_k), np.asarray(t_r))
    np.testing.assert_array_equal(np.asarray(h_k), np.asarray(h_r))


def test_lru_kernel_matches_core_simulator():
    """The parallel kernel agrees with the sequential core.cachesim LLC on
    a single-level workload (directory semantics, no back-invalidation in
    play: distinct sets, cotenant-only accesses)."""
    from repro.core import cachesim
    geom = cachesim.MachineGeometry(
        n_domains=1, cores_per_domain=1,
        llc=cachesim.CacheGeometry(n_sets=16, n_ways=4, n_slices=1))
    state = cachesim.init_machine(geom)
    rng = np.random.default_rng(7)
    blocks = (rng.integers(0, 64, size=128) * 16 +
              rng.integers(0, 16, size=128)).astype(np.int32)
    state, _ = cachesim.access_stream(
        state, geom, jnp.asarray(blocks), jnp.zeros(128, jnp.int32),
        jnp.ones(128, bool))
    # same accesses through the kernel, partitioned per set
    tags = np.full((16, 4), -1, np.int32)
    age = np.zeros((16, 4), np.int32)
    per_set = [[] for _ in range(16)]
    for i, b in enumerate(blocks):
        per_set[b % 16].append((i, b))
    T = max(len(s) for s in per_set)
    streams = np.full((16, T), -1, np.int32)
    clocks = np.zeros((16, T), np.int64)
    for s, items in enumerate(per_set):
        for j, (i, b) in enumerate(items):
            streams[s, j] = b
    t_k, _, _ = sim_ops.simulate_rows(jnp.asarray(tags), jnp.asarray(age),
                                      jnp.asarray(streams))
    kernel_sets = {s: set(int(x) for x in np.asarray(t_k[s]) if x >= 0)
                   for s in range(16)}
    core_tags = np.asarray(state["llc"][0][0, 0])  # (sets, ways)
    core_sets = {s: set(int(x) for x in core_tags[s] if x >= 0)
                 for s in range(16)}
    # LRU content per set must match (ages differ: local vs global clock —
    # LRU *order* within a set is preserved by order-preserving clocks)
    assert kernel_sets == core_sets


# -- cachesim step: deterministic interpret-mode parity sweep --------------------------

@pytest.mark.parametrize("rows,ways,T,seed", [
    (4, 4, 1, 0),
    (8, 8, 33, 1),      # T not a multiple of anything
    (16, 4, 48, 2),
    (32, 8, 17, 3),
])
def test_lru_kernel_parity_sweep(rows, ways, T, seed):
    """cachesim_step Pallas kernel vs ref.py oracle, interpret mode on CPU
    (deterministic companion to the property test above)."""
    rng = np.random.default_rng(seed)
    tags = np.full((rows, ways), -1, np.int32)
    tags[: rows // 2, : ways // 2] = rng.integers(0, 64, (rows // 2,
                                                          ways // 2))
    age = np.zeros((rows, ways), np.int32)
    streams = rng.integers(-1, 64, size=(rows, T)).astype(np.int32)
    t_k, a_k, h_k = sim_ops.simulate_rows(jnp.asarray(tags), jnp.asarray(age),
                                          jnp.asarray(streams))
    t_r, a_r, h_r = sim_ref.lru_sets_ref(jnp.asarray(tags), jnp.asarray(age),
                                         jnp.asarray(streams))
    np.testing.assert_array_equal(np.asarray(t_k), np.asarray(t_r))
    np.testing.assert_array_equal(np.asarray(a_k), np.asarray(a_r))
    np.testing.assert_array_equal(np.asarray(h_k), np.asarray(h_r))


# -- cache probe ------------------------------------------------------------------------

@pytest.mark.parametrize("lanes,ways,T,seed", [
    (8, 4, 24, 0),
    (16, 8, 40, 1),
    (64, 8, 40, 2),
    (32, 16, 12, 3),    # short prime, high associativity
    (384, 11, 20, 4),   # multi-block grid (3 x 128 lanes), Table 1 ways
])
def test_prime_probe_kernel_parity(lanes, ways, T, seed):
    """Batched Prime+Probe verdict kernel vs ref.py oracle (interpret mode),
    with pre-populated lane states."""
    rng = np.random.default_rng(seed)
    tags = np.full((lanes, ways), -1, np.int32)
    tags[::2, : ways // 2] = rng.integers(100, 164, (lanes // 2, ways // 2))
    age = np.zeros((lanes, ways), np.int32)
    streams = rng.integers(-1, 64, (lanes, T)).astype(np.int32)
    targets = rng.integers(0, 64, lanes).astype(np.int32)
    k = np.asarray(probe_ops.probe_verdicts(
        jnp.asarray(tags), jnp.asarray(age), jnp.asarray(streams),
        jnp.asarray(targets)))
    r = np.asarray(probe_ref.prime_probe_ref(
        jnp.asarray(tags), jnp.asarray(age), jnp.asarray(streams),
        jnp.asarray(targets)))
    np.testing.assert_array_equal(k, r)


def test_prime_probe_kernel_lru_eviction_law():
    """Under LRU, the verdict obeys the conflict-eviction law the probing
    stack relies on: evicted iff >= ways distinct other blocks follow the
    target's install (independent of pre-existing lane residents)."""
    rng = np.random.default_rng(7)
    lanes, ways, T = 32, 8, 48
    tags = np.full((lanes, ways), -1, np.int32)
    tags[::2, :4] = rng.integers(1000, 1064, (lanes // 2, 4))
    age = np.zeros((lanes, ways), np.int32)
    targets = rng.integers(0, 64, lanes).astype(np.int32)
    streams = rng.integers(-1, 64, (lanes, T)).astype(np.int32)
    streams[streams == targets[:, None]] = -1    # no in-stream refresh
    v = np.asarray(probe_ops.probe_verdicts(
        jnp.asarray(tags), jnp.asarray(age), jnp.asarray(streams),
        jnp.asarray(targets)))
    for b in range(lanes):
        distinct = len(set(int(x) for x in streams[b] if x >= 0))
        assert bool(v[b]) == (distinct >= ways), (b, distinct)


@pytest.mark.parametrize("rows,block", [(512, 512), (1024, 256), (64, 64)])
def test_triad_kernel(rows, block):
    a = jnp.arange(rows * 128, dtype=jnp.float32).reshape(rows, 128)
    b = jnp.ones((rows, 128), jnp.float32) * 2
    s = jnp.asarray([3.0], jnp.float32)
    out = triad(a, b, s, block=block, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(probe_ref.triad_ref(a, b, 3.0)))


def test_measure_bandwidth_runs():
    bw, dt = probe_ops.measure_hbm_bandwidth(n_bytes=3 * (1 << 18), reps=1)
    assert bw > 0 and dt > 0


@pytest.mark.parametrize("mib", [64, 256])
def test_measure_bandwidth_rows_are_whole_triad_blocks(mib):
    """PodMonitor's probe sizes: rows must be a whole number of triad
    blocks or the kernel's grid assertion trips."""
    rows = probe_ops.triad_rows(mib << 20)
    assert rows % TRIAD_BLOCK == 0 and rows * 128 * 12 <= mib << 20
    bw, dt = probe_ops.measure_hbm_bandwidth(n_bytes=mib << 20, reps=1)
    assert bw > 0 and dt > 0


@pytest.mark.parametrize("n,block", [(2048, 256), (384, 128), (64, 64),
                                     (200, 200)])
def test_lane_block_is_128_multiple_or_whole(n, block):
    assert _compat.lane_block(n) == block


def test_interpret_mode_only_on_cpu(monkeypatch):
    monkeypatch.setattr(_compat.jax, "default_backend", lambda: "cpu")
    assert _compat.interpret_mode() is True
    monkeypatch.setattr(_compat.jax, "default_backend", lambda: "tpu")
    assert _compat.interpret_mode() is False
    monkeypatch.setattr(_compat.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        _compat.interpret_mode()
