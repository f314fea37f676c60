"""Bit-exact set-associative cache-hierarchy simulator in JAX.

This is the "hardware" the paper-faithful CacheX reproduction runs against.
It models the memory system of the paper's evaluation platform (Intel
Skylake-SP Gold 6138, Table 1):

  * per-core private L2 (1 MB, 16-way, 1024 sets); L1 is not modelled — no
    claim in the paper depends on L1/L2 distinction, only on the
    private-cache vs shared-LLC vs DRAM latency classes,
  * a sliced, shared LLC (11-way, 2048 sets/slice, N slices) with
    *directory semantics*: the modelled "LLC entry" is the combined
    LLC + snoop-filter directory entry of Skylake's non-inclusive design.
    Every line cached in any core's private cache has such an entry; every
    access references it (so priming an eviction set always exerts pressure
    on the target set even when the lines are L2-resident — on real SKX the
    L2 is 16-way while the LLC is 11-way, so LLC-congruent lines fit in L2
    and conflict pressure arrives via the inclusive *directory*; this is
    precisely the mechanism of Yan et al. [70] that L2FBS [73] builds on);
    evicting the entry back-invalidates the line from every private cache in
    the domain.  All eviction-set semantics the paper relies on are identical
    under this abstraction.
  * LLC slice selection via a hidden hash of the block address (the
    "uncontrollable" slice bits of paper §3.1/§3.2),
  * true-LRU replacement per set (the construction algorithms must not rely
    on it — tests also exercise the ``random`` policy).

State lives in dense JAX arrays; every access is one straight-line
(branch-free, predicated) ``lax.scan`` step, so whole access streams run as
a single jitted call.  Addresses are *block addresses* (HPA >> 6) stored as
int32.  ``-1`` marks an empty way and pads access streams to static shapes
(padding accesses are no-ops).

Accesses carry the issuing core: each core has a private L2; each domain of
``cores_per_domain`` cores shares one LLC.  Co-tenant VM accesses only touch
the LLC of their domain (their private caches are irrelevant to the probing
VM) but *do* back-invalidate the prober's private lines on LLC eviction —
the mechanism Prime+Probe depends on.  ``MachineGeometry.inclusion``
selects the directory variant: ``"inclusive"`` (the default, modelled
above) back-invalidates; ``"non_inclusive"`` lets L2-resident lines
survive LLC eviction (see `repro.core.hierarchy` for the probing
consequences of each).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import trace

LINE_BITS = 6   # 64-byte cache lines
PAGE_BITS = 12  # 4 kB pages
BLOCKS_PER_PAGE = 1 << (PAGE_BITS - LINE_BITS)  # 64

# Simulated access latencies (cycles) by hit level.
LAT_L2, LAT_LLC, LAT_DRAM = 14, 50, 200
# Thresholds used by probing code ("was this evicted from L2 / the LLC?").
L2_MISS_THRESHOLD = (LAT_L2 + LAT_LLC) // 2     # 32
LLC_MISS_THRESHOLD = (LAT_LLC + LAT_DRAM) // 2  # 125


@dataclasses.dataclass(frozen=True)
class CacheGeometry:
    n_sets: int
    n_ways: int
    n_slices: int = 1

    @property
    def n_lines(self) -> int:
        return self.n_sets * self.n_ways * self.n_slices

    @property
    def size_bytes(self) -> int:
        return self.n_lines << LINE_BITS


# Paper Table 1 geometries.
SKYLAKE_L2 = CacheGeometry(n_sets=1024, n_ways=16)


def skylake_llc(n_slices: int = 20, n_ways: int = 11) -> CacheGeometry:
    return CacheGeometry(n_sets=2048, n_ways=n_ways, n_slices=n_slices)


def slice_hash(block_addr, n_slices: int, seed: int = 0x9E3779B9):
    """Balanced hidden hash of the block address -> LLC slice id.

    Real Intel CPUs use an undocumented XOR-based hash of HPA bits [63:6]
    (McCalpin '21).  Any balanced hash that depends on bits above the guest's
    control preserves the properties the paper relies on.  xorshift-multiply
    mix; balance is asserted in tests/test_cachesim.py.
    """
    if n_slices == 1:
        return jnp.zeros_like(block_addr, dtype=jnp.int32)
    x = block_addr.astype(jnp.uint32) * jnp.uint32(seed)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 16)
    return (x % jnp.uint32(n_slices)).astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class MachineGeometry:
    """`n_domains` LLC domains, each with `cores_per_domain` private-L2 cores.

    ``inclusion`` selects the hierarchy variant (paper platforms mix both):

      * ``"inclusive"`` — the LLC entry doubles as the inclusive directory
        entry (Skylake's snoop filter, Yan et al. [70]): evicting it
        back-invalidates the line from every private L2 in the domain.
        This is what makes LLC eviction sets observable from L2-resident
        lines — and what milan_ccx's small LLC aliases through.
      * ``"non_inclusive"`` — no back-invalidation: an L2-resident line
        survives its LLC/directory entry being evicted (a victim-cache /
        exclusive-leaning design).  LLC probing then only observes lines
        that actually left the private level, so per-level attribution
        must probe each level on its own terms.
    """

    n_domains: int = 1
    cores_per_domain: int = 2
    l2: CacheGeometry = SKYLAKE_L2
    llc: CacheGeometry = dataclasses.field(default_factory=lambda: skylake_llc(4))
    replacement: str = "lru"  # "lru" | "random"
    slice_seed: int = 0x9E3779B9
    inclusion: str = "inclusive"  # "inclusive" | "non_inclusive"

    @property
    def n_cores(self) -> int:
        return self.n_domains * self.cores_per_domain


def init_machine(geom: MachineGeometry):
    return {
        "l2": (jnp.full((geom.n_cores, geom.l2.n_sets, geom.l2.n_ways), -1, jnp.int32),
               jnp.zeros((geom.n_cores, geom.l2.n_sets, geom.l2.n_ways), jnp.int32)),
        "llc": (jnp.full((geom.n_domains, geom.llc.n_slices, geom.llc.n_sets,
                          geom.llc.n_ways), -1, jnp.int32),
                jnp.zeros((geom.n_domains, geom.llc.n_slices, geom.llc.n_sets,
                           geom.llc.n_ways), jnp.int32)),
        "clock": jnp.zeros((), jnp.int32),
        "rng": jnp.uint32(0x12345678),
    }


def _next_rand(rng):
    rng = rng ^ (rng << 13)
    rng = rng ^ (rng >> 17)
    rng = rng ^ (rng << 5)
    return rng, (rng >> 1).astype(jnp.int32)


def _touch(tags_row, age_row, clock, block, rand_bits):
    """Predicated access of one set row: (tags, age, hit, victim_block)."""
    hit_mask = tags_row == block
    hit = jnp.any(hit_mask)
    empty_mask = tags_row == -1
    has_empty = jnp.any(empty_mask)
    lru_way = jnp.argmin(jnp.where(empty_mask, jnp.iinfo(jnp.int32).max, age_row))
    rand_way = jnp.where(rand_bits >= 0, rand_bits % tags_row.shape[0], 0)
    repl_way = jnp.where(rand_bits >= 0, rand_way, lru_way)
    victim_way = jnp.where(has_empty, jnp.argmax(empty_mask), repl_way)
    way = jnp.where(hit, jnp.argmax(hit_mask), victim_way)
    victim = jnp.where(hit | has_empty, -1, tags_row[victim_way])
    return tags_row.at[way].set(block), age_row.at[way].set(clock), hit, victim


def _access_one(state, geom: MachineGeometry, core, block, cotenant):
    """One access, fully branch-free (predicated row updates)."""
    clock = state["clock"] + 1
    rng = state["rng"]
    if geom.replacement == "random":
        rng, rand_bits = _next_rand(rng)
    else:
        rand_bits = jnp.int32(-1)

    l2_tags, l2_age = state["l2"]
    llc_tags, llc_age = state["llc"]

    valid = block >= 0
    safe_block = jnp.where(valid, block, 0)
    is_prober = valid & ~cotenant
    domain = core // geom.cores_per_domain
    l2_set = (safe_block % geom.l2.n_sets).astype(jnp.int32)
    llc_set = (safe_block % geom.llc.n_sets).astype(jnp.int32)
    llc_slice = slice_hash(safe_block, geom.llc.n_slices, geom.slice_seed)

    # ---- private L2 (prober only) ----
    r2t, r2a = l2_tags[core, l2_set], l2_age[core, l2_set]
    n2t, n2a, l2_hit, _ = _touch(r2t, r2a, clock, safe_block, rand_bits)
    l2_tags = l2_tags.at[core, l2_set].set(jnp.where(is_prober, n2t, r2t))
    l2_age = l2_age.at[core, l2_set].set(jnp.where(is_prober, n2a, r2a))
    l2_hit = l2_hit & is_prober

    # ---- shared LLC/directory (every valid access) ----
    rlt = llc_tags[domain, llc_slice, llc_set]
    rla = llc_age[domain, llc_slice, llc_set]
    nlt, nla, llc_hit, victim = _touch(rlt, rla, clock, safe_block, rand_bits)
    llc_tags = llc_tags.at[domain, llc_slice, llc_set].set(
        jnp.where(valid, nlt, rlt))
    llc_age = llc_age.at[domain, llc_slice, llc_set].set(
        jnp.where(valid, nla, rla))
    victim = jnp.where(valid, victim, -1)

    # ---- back-invalidation of the directory victim from this domain's cores
    # (inclusive hierarchies only: `geom` is a static jit key, so this
    # Python branch compiles the non-inclusive variant without the work)
    if geom.inclusion == "inclusive":
        has_victim = victim >= 0
        safe_victim = jnp.where(has_victim, victim, 0)
        v_set = (safe_victim % geom.l2.n_sets).astype(jnp.int32)
        core_ids = jnp.arange(geom.n_cores, dtype=jnp.int32)
        in_domain = (core_ids // geom.cores_per_domain) == domain
        rows = l2_tags[:, v_set]  # (n_cores, ways)
        inval = (has_victim & in_domain)[:, None] & (rows == safe_victim)
        l2_tags = l2_tags.at[:, v_set].set(jnp.where(inval, -1, rows))

    lat = jnp.where(~valid, 0,
                    jnp.where(l2_hit, LAT_L2,
                              jnp.where(llc_hit, LAT_LLC, LAT_DRAM)))

    return {"l2": (l2_tags, l2_age), "llc": (llc_tags, llc_age),
            "clock": clock, "rng": rng}, lat.astype(jnp.int32)


def _stream_scan(state, geom: MachineGeometry, blocks, cores, cotenant):
    def step(st, x):
        blk, core, ct = x
        return _access_one(st, geom, core, blk, ct)
    return jax.lax.scan(step, state, (blocks, cores, cotenant))


@functools.partial(jax.jit, static_argnames=("geom",), donate_argnums=(0,))
def access_stream(state, geom: MachineGeometry, blocks, cores, cotenant):
    """Run a 1-D stream of accesses. Returns (state, latencies)."""
    return _stream_scan(state, geom, blocks, cores, cotenant)


@functools.partial(jax.jit, static_argnames=("geom",), donate_argnums=(0,))
def access_streams_committed(states, geom: MachineGeometry, blocks, cores,
                             cotenant):
    """G independent machines each run (and COMMIT) their own access stream
    in one jitted dispatch: `access_stream` vmapped over stacked machine
    states.  ``states`` is a machine-state pytree with a leading guest axis
    (see :func:`stack_states`); ``blocks``/``cores``/``cotenant`` are
    (G, T).  Returns (states, latencies (G, T)).

    This is the multi-guest lowering target of committed ProbePlan ops
    (prime / traverse): each guest's lane is bit-identical to running its
    stream alone through :func:`access_stream` from its own state (integer
    arithmetic throughout — vmap changes nothing).
    """
    return jax.vmap(
        lambda s, b, c, t: _stream_scan(s, geom, b, c, t))(
            states, blocks, cores, cotenant)


@jax.jit
def _stack(states):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)


@functools.partial(jax.jit, static_argnums=(1,))
def _unstack(states, n):
    return tuple(jax.tree_util.tree_map(lambda x: x[i], states)
                 for i in range(n))


def stack_states(states):
    """Stack per-guest machine states into one pytree with a leading guest
    axis (host-side helper for the multi-guest dispatch paths): one
    compiled program per call, per guest count and geometry.  The inputs
    are copied, not donated, so the per-guest states stay usable."""
    trace.count("staging_dispatches")
    return _stack(states)


def unstack_states(states, n: int):
    """Split a stacked machine-state pytree back into ``n`` per-guest
    states, in one compiled program per call."""
    trace.count("staging_dispatches")
    return list(_unstack(states, n))


# Per-lane rng fork for the batched engine.  Lane 0 keeps the machine rng
# verbatim so a single-lane batched call is bit-identical to access_stream.
RNG_LANE_STRIDE = 0x9E3779B1


def _measure_lanes(state, geom: MachineGeometry, blocks, cores, cotenant,
                   salt):
    def lane(rng, blk_row, core, ct):
        st = dict(state)
        st["rng"] = rng

        def step(s, b):
            return _access_one(s, geom, core, b, ct)

        _, lats = jax.lax.scan(step, st, blk_row)
        return lats

    n_lanes = blocks.shape[0]
    rngs = (state["rng"] + jnp.uint32(salt) * jnp.uint32(0x7F4A7C15) +
            jnp.arange(n_lanes, dtype=jnp.uint32) * jnp.uint32(RNG_LANE_STRIDE))
    return jax.vmap(lane)(rngs, blocks, cores, cotenant)


@functools.partial(jax.jit, static_argnames=("geom",))
def access_streams_batched(state, geom: MachineGeometry, blocks, cores,
                           cotenant, salt=jnp.uint32(0)):
    """Batched multi-set Prime+Probe engine: B independent access streams,
    each run against a snapshot of ``state``, in ONE jitted dispatch.

    ``blocks``: (B, T) int32, -1 padded; ``cores``: (B,) int32 (one issuing
    core per lane); ``cotenant``: (B,) bool.  Returns latencies (B, T).

    Lane state mutations are NOT committed: the engine implements
    *measurement* probes.  Under LRU this is exact — an eviction test
    ``[target, candidates..., target]`` installs the target first, so its
    outcome depends only on the same-set accesses inside its own lane, never
    on what other lanes (or earlier tests) left behind; see
    tests/test_platforms.py for the equivalence property.  Under ``random``
    replacement each lane forks the machine rng by ``RNG_LANE_STRIDE * lane``
    (lane 0 with ``salt=0`` keeps the machine rng, so a one-lane batched
    call is bit-exact vs. the sequential scan path).  ``salt`` re-forks
    every lane — majority-vote callers pass the vote index so repeated
    probes of one snapshot draw independent replacement decisions rather
    than replaying the identical trial.
    """
    return _measure_lanes(state, geom, blocks, cores, cotenant, salt)


@functools.partial(jax.jit, static_argnames=("geom",))
def access_streams_batched_multi(states, geom: MachineGeometry, blocks,
                                 cores, cotenant, salts):
    """The batched engine vmapped over guests: G machines × B measurement
    lanes × T accesses in ONE jitted dispatch.  ``states`` has a leading
    guest axis (:func:`stack_states`); ``blocks``: (G, B, T); ``cores``/
    ``cotenant``: (G, B); ``salts``: (G,) uint32 (each guest's own salt —
    per-lane rng forks depend only on the guest's machine rng, its salt and
    the lane index, so every guest's latencies are bit-identical to a
    standalone :func:`access_streams_batched` call on its own state).
    Returns latencies (G, B, T).
    """
    return jax.vmap(
        lambda s, b, c, t, sa: _measure_lanes(s, geom, b, c, t, sa))(
            states, blocks, cores, cotenant, salts)


# ---------------------------------------------------------------------------
# Host-side oracle helpers (ground truth NOT visible to the simulated VM;
# the analogue of the paper's custom GPA->HPA hypercall used for validation).
# ---------------------------------------------------------------------------

def resident_level(state, block: int, core: int, geom: MachineGeometry) -> int:
    """2/3 if block is in this core's L2 / its domain's LLC, else 0."""
    domain = core // geom.cores_per_domain
    if (np.asarray(state["l2"][0][core]) == block).any():
        return 2
    if (np.asarray(state["llc"][0][domain]) == block).any():
        return 3
    return 0


def llc_occupancy(state, domain: int = 0) -> np.ndarray:
    """(n_slices, n_sets) count of valid lines per LLC set."""
    tags = np.asarray(state["llc"][0][domain])
    return (tags >= 0).sum(axis=-1)
