"""Plain numpy model of the simulated two-level cache hierarchy.

This is the yardstick the benchmark holds the program's cache engines to.
It imports nothing of the program: the semantics are written down here
again, from the model's description, and every engine call the timed
window made is replayed through it and compared element for element.

The modelled machine: ``n_domains`` LLC domains of ``cores_per_domain``
cores.  Each core has a private L2 (``l2_sets`` x ``l2_ways``); each domain
shares a sliced LLC (``llc_slices`` x ``llc_sets`` x ``llc_ways``) whose
entry doubles as the directory entry.  Addresses are 64-byte block
numbers; ``-1`` is an empty way, and in a stream a no-op access.

One access of block ``b`` from ``core`` (``cotenant`` marks another VM's
access, which never fills the prober's L2):

1. the machine clock ticks; under ``random`` replacement a xorshift32 step
   draws the replacement bits, which both levels use;
2. the core's L2 set ``b % l2_sets`` is touched (prober accesses only);
3. the domain's LLC set ``b % llc_sets`` in slice ``slice_of(b)`` is
   touched by every valid access;
4. an inclusive hierarchy back-invalidates the LLC victim from every L2 of
   the domain;
5. the latency is 14 cycles on an L2 hit, 50 on an LLC hit, 200 otherwise,
   and 0 for a padding access.

Touching a set row: a hit refreshes the hit way's age to the clock; a miss
fills the first empty way, or else evicts the least recently used way (the
first of equal ages) or, under ``random``, way ``bits % ways``.

Measurement lanes (the batched engines) each run from a copy of the
machine state whose rng is forked per lane and per call salt; they commit
nothing.  Committed streams run one machine per guest and return the new
state.

``dtype`` is the integer type tags, ages, the clock and block numbers are
held in.  The model is stated in 32 bits; a narrower type is the control
that must come out wrong.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np

LAT_L2, LAT_LLC, LAT_DRAM = 14, 50, 200
LANE_SALT_MUL = 0x7F4A7C15
LANE_STRIDE = 0x9E3779B1
HASH_MUL = 0x85EBCA6B
U32 = np.uint32


@dataclasses.dataclass(frozen=True)
class Machine:
    n_domains: int
    cores_per_domain: int
    l2_sets: int
    l2_ways: int
    llc_sets: int
    llc_ways: int
    llc_slices: int
    replacement: str = "lru"
    slice_seed: int = 0x9E3779B9
    inclusive: bool = True

    @property
    def n_cores(self) -> int:
        return self.n_domains * self.cores_per_domain


def slice_of(blocks: np.ndarray, n_slices: int, seed: int) -> np.ndarray:
    """The hidden slice hash: xorshift-multiply of the block number."""
    blocks = np.asarray(blocks)
    if n_slices == 1:
        return np.zeros(blocks.shape, np.int64)
    with np.errstate(over="ignore"):
        x = blocks.astype(np.int64).astype(U32) * U32(seed)
        x = x ^ (x >> U32(13))
        x = x * U32(HASH_MUL)
        x = x ^ (x >> U32(16))
    return (x % U32(n_slices)).astype(np.int64)


def _xorshift(rng: np.ndarray):
    with np.errstate(over="ignore"):
        rng = rng ^ (rng << U32(13))
        rng = rng ^ (rng >> U32(17))
        rng = rng ^ (rng << U32(5))
    return rng, (rng >> U32(1)).astype(np.int64)


def _touch(tags, ages, block, clock, bits):
    """Touch one set row per machine.  ``tags``/``ages``: (N, ways);
    ``block``/``clock``/``bits``: (N,).  Returns new rows, hit, victim."""
    ways = tags.shape[1]
    hit_mask = tags == block[:, None]
    hit = hit_mask.any(axis=1)
    empty = tags == -1
    has_empty = empty.any(axis=1)
    lru_way = np.argmin(np.where(empty, np.iinfo(np.int64).max,
                                 ages.astype(np.int64)), axis=1)
    repl_way = np.where(bits >= 0, np.where(bits >= 0, bits, 0) % ways,
                        lru_way)
    victim_way = np.where(has_empty, np.argmax(empty, axis=1), repl_way)
    way = np.where(hit, np.argmax(hit_mask, axis=1), victim_way)
    rows = np.arange(len(tags))
    victim = np.where(hit | has_empty, -1, tags[rows, victim_way])
    new_tags = tags.copy()
    new_ages = ages.copy()
    new_tags[rows, way] = block
    new_ages[rows, way] = clock
    return new_tags, new_ages, hit, victim


def run(m: Machine, state: Dict[str, np.ndarray], blocks, cores, cotenant,
        dtype=np.int32):
    """Run N independent machines, each through its own stream.

    ``state`` holds per-machine arrays with a leading axis N:
    ``l2_tags``/``l2_age`` (N, cores, sets, ways), ``llc_tags``/``llc_age``
    (N, domains, slices, sets, ways), ``clock`` (N,), ``rng`` (N,) uint32.
    ``blocks``/``cores``/``cotenant`` are (N, T).  Returns the new state
    and the latencies (N, T) as int32.  The state is updated in place."""
    s = {k: np.array(v, dtype=(U32 if k == "rng" else dtype))
         for k, v in state.items()}
    blocks = np.asarray(blocks).astype(dtype)
    cores = np.asarray(cores, np.int64)
    cotenant = np.asarray(cotenant, bool)
    n, t_len = blocks.shape
    idx = np.arange(n)
    core_ids = np.arange(m.n_cores)
    lats = np.zeros((n, t_len), np.int32)
    for t in range(t_len):
        with np.errstate(over="ignore"):
            s["clock"] = (s["clock"] + 1).astype(dtype)
        if m.replacement == "random":
            s["rng"], bits = _xorshift(s["rng"])
        else:
            bits = np.full(n, -1, np.int64)
        blk = blocks[:, t]
        core = cores[:, t]
        valid = blk >= 0
        safe = np.where(valid, blk, 0).astype(dtype)
        prober = valid & ~cotenant[:, t]
        domain = core // m.cores_per_domain
        l2_set = safe.astype(np.int64) % m.l2_sets
        llc_set = safe.astype(np.int64) % m.llc_sets
        llc_slice = slice_of(safe, m.llc_slices, m.slice_seed)

        old_t = s["l2_tags"][idx, core, l2_set]
        old_a = s["l2_age"][idx, core, l2_set]
        new_t, new_a, l2_hit, _ = _touch(old_t, old_a, safe, s["clock"], bits)
        s["l2_tags"][idx, core, l2_set] = np.where(prober[:, None], new_t,
                                                   old_t)
        s["l2_age"][idx, core, l2_set] = np.where(prober[:, None], new_a,
                                                  old_a)
        l2_hit &= prober

        old_t = s["llc_tags"][idx, domain, llc_slice, llc_set]
        old_a = s["llc_age"][idx, domain, llc_slice, llc_set]
        new_t, new_a, llc_hit, victim = _touch(old_t, old_a, safe,
                                               s["clock"], bits)
        s["llc_tags"][idx, domain, llc_slice, llc_set] = np.where(
            valid[:, None], new_t, old_t)
        s["llc_age"][idx, domain, llc_slice, llc_set] = np.where(
            valid[:, None], new_a, old_a)
        victim = np.where(valid, victim, -1)

        if m.inclusive:
            has_v = victim >= 0
            v_set = np.where(has_v, victim, 0).astype(np.int64) % m.l2_sets
            rows = s["l2_tags"][idx, :, v_set]            # (N, cores, ways)
            in_dom = (core_ids[None, :] // m.cores_per_domain
                      == domain[:, None])
            inval = ((has_v[:, None] & in_dom)[:, :, None]
                     & (rows == victim[:, None, None]))
            s["l2_tags"][idx, :, v_set] = np.where(inval, -1, rows)

        lats[:, t] = np.where(~valid, 0, np.where(
            l2_hit, LAT_L2, np.where(llc_hit, LAT_LLC, LAT_DRAM)))
    return s, lats


def lane_rngs(rng: int, salt: int, n_lanes: int) -> np.ndarray:
    """Per-lane rng fork of a measurement call (lane 0 with salt 0 keeps
    the machine's rng)."""
    with np.errstate(over="ignore"):
        return (U32(rng) + U32(salt) * U32(LANE_SALT_MUL)
                + np.arange(n_lanes, dtype=U32) * U32(LANE_STRIDE))


def _repeat(state: Dict[str, np.ndarray], n: int) -> Dict[str, np.ndarray]:
    return {k: np.repeat(np.asarray(v)[None], n, axis=0)
            for k, v in state.items()}


def stream(m: Machine, state, blocks, cores, cotenant, dtype=np.int32):
    """One committed stream on one machine (``state`` without the leading
    axis).  Returns (new state, latencies (T,))."""
    new, lats = run(m, _repeat(state, 1), np.asarray(blocks)[None],
                    np.asarray(cores)[None], np.asarray(cotenant)[None],
                    dtype)
    return {k: v[0] for k, v in new.items()}, lats[0]


def committed(m: Machine, states, blocks, cores, cotenant, dtype=np.int32):
    """G machines (leading axis in ``states``), each committing its own
    (T,) stream.  Returns (new states, latencies (G, T))."""
    return run(m, states, blocks, cores, cotenant, dtype)


def batched(m: Machine, state, blocks, cores, cotenant, salt,
            dtype=np.int32):
    """B uncommitted measurement lanes from one machine's state: lane i
    runs ``blocks[i]`` from core ``cores[i]``.  Returns latencies (B, T)."""
    blocks = np.asarray(blocks)
    b, t_len = blocks.shape
    lanes = _repeat(state, b)
    lanes["rng"] = lane_rngs(int(state["rng"]), int(salt), b)
    _, lats = run(m, lanes, blocks,
                  np.repeat(np.asarray(cores)[:, None], t_len, axis=1),
                  np.repeat(np.asarray(cotenant)[:, None], t_len, axis=1),
                  dtype)
    return lats


def batched_multi(m: Machine, states, blocks, cores, cotenant, salts,
                  dtype=np.int32):
    """``batched`` for G machines at once: ``blocks`` (G, B, T), ``cores``
    and ``cotenant`` (G, B), ``salts`` (G,).  Returns (G, B, T)."""
    blocks = np.asarray(blocks)
    g, b, t_len = blocks.shape
    lanes = {k: np.repeat(np.asarray(v), b, axis=0) for k, v in
             states.items()}
    lanes["rng"] = np.concatenate([
        lane_rngs(int(r), int(sa), b)
        for r, sa in zip(np.asarray(states["rng"]), np.asarray(salts))])
    cores = np.asarray(cores).reshape(g * b)
    cotenant = np.asarray(cotenant).reshape(g * b)
    _, lats = run(m, lanes, blocks.reshape(g * b, t_len),
                  np.repeat(cores[:, None], t_len, axis=1),
                  np.repeat(cotenant[:, None], t_len, axis=1), dtype)
    return lats.reshape(g, b, t_len)


def fleet_progress(domain_idx, rates, duty_period, duty_on, sens, ipc0,
                   slowdown, noise_dom, scale, n_domains, ticks,
                   dtype=np.float32):
    """One fleet interval's progress model: per tick, a workload bursts
    while ``t % duty_period < duty_on``; a domain's traffic is its bursting
    workloads' rates plus the background noise, times ``scale``; a
    workload progresses ``ipc0 / ((1 + sens * contention) * slowdown)``.
    Returns (progress summed over ticks, mean contention per domain)."""
    f = lambda x: np.asarray(x).astype(dtype)
    t = np.arange(ticks)
    active = (t[None, :] % np.asarray(duty_period)[:, None]
              < np.asarray(duty_on)[:, None])
    inj = f(rates)[:, None] * active.astype(dtype)
    traffic = np.zeros((n_domains, ticks), dtype)
    np.add.at(traffic, np.asarray(domain_idx), inj)
    traffic = traffic + f(noise_dom)[:, None]
    cont = traffic * dtype(scale)
    d = np.asarray(domain_idx)
    per_tick = f(ipc0)[:, None] / ((dtype(1.0) + f(sens)[:, None] * cont[d])
                                   * f(slowdown)[:, None])
    return (per_tick.sum(axis=1, dtype=dtype),
            cont.mean(axis=1, dtype=dtype))


# ---------------------------------------------------------------------------
# the contention monitor and the placement rules fed by it
# ---------------------------------------------------------------------------

#: a probed line counts as evicted from a level when its latency is above
#: the midpoint between that level's hit and the next level's
MISS_THRESHOLD = {"l2": (LAT_L2 + LAT_LLC) // 2,
                  "llc": (LAT_LLC + LAT_DRAM) // 2}
#: intervals a contention tier (CAS) or a hottest color (CAP) must hold
#: before it is committed
HYSTERESIS = 3


def set_rates(lanes, order, levels, window_ms, dtype=np.float64):
    """One monitoring interval's per-set eviction rate (% of lines per
    ms): lane ``i`` probed monitored set ``order[i]``; a set's fraction is
    the share of its lines above its level's miss threshold."""
    rate = np.zeros(len(levels), dtype)
    for i, lats in zip(order, lanes):
        frac = np.mean(np.asarray(lats) > MISS_THRESHOLD[levels[i]])
        rate[i] = dtype(100.0) * dtype(frac) / dtype(max(window_ms, 1e-9))
    return rate


def ewma_views(rates_by_interval, live_by_interval, llc, domains, colors,
               alpha, dtype=np.float64):
    """The monitor's published rates: per monitored set an exponentially
    weighted mean of its rate (a set that is not live keeps its value),
    then the mean over the live LLC sets of each domain and of each color.
    Returns one (per_domain, per_color) pair of dicts per interval."""
    out = []
    ewma = None
    a = dtype(alpha)
    llc, domains, colors = (np.asarray(x) for x in (llc, domains, colors))
    for rate, live in zip(rates_by_interval, live_by_interval):
        rate = np.asarray(rate).astype(dtype)
        if ewma is None:
            ewma = np.zeros(len(rate), dtype)
        ewma = np.where(live, (dtype(1) - a) * ewma + a * rate, ewma)
        agg = np.asarray(live) & llc
        out.append(tuple(
            {int(k): float(np.mean(ewma[agg & (keys == k)]))
             for k in np.unique(keys[agg])} for keys in (domains, colors)))
    return out


def tiers(per_domain_views, thresholds):
    """CAS's committed contention tier per domain after each view: a
    domain's instant tier is the first threshold its rate is below (or
    one past the last); it is committed once it has moved the same way
    for ``HYSTERESIS`` views in a row.  Unseen domains are tier 0."""
    tier: Dict[int, int] = {}
    pending: Dict[int, tuple] = {}
    out = []
    for rates in per_domain_views:
        for d, r in rates.items():
            cur = tier.setdefault(d, 0)
            inst = next((i for i, t in enumerate(thresholds) if r < t),
                        len(thresholds))
            step = (inst > cur) - (inst < cur)
            pdir, cnt = pending.get(d, (0, 0))
            if step == 0:
                pending[d] = (0, 0)
                continue
            cnt = cnt + 1 if step == pdir else 1
            if cnt >= HYSTERESIS:
                tier[d], pending[d] = inst, (0, 0)
            else:
                pending[d] = (step, cnt)
        out.append(dict(tier))
    return out


def cas_misplaced(task_vcpus, vcpu_domain, tier) -> bool:
    """CAS places each waking task on an idle vCPU of the least contended
    tier that has one, so the tasks fill tiers from the best: for every
    tier ``t``, the tasks on domains of tier <= ``t`` are as many as the
    vCPUs there allow.  True when the placement breaks that, or puts two
    tasks on one vCPU."""
    if len(set(task_vcpus)) != len(task_vcpus):
        return True
    of = lambda v: tier.get(vcpu_domain[v], 0)
    vcpu_tiers = [of(v) for v in vcpu_domain]
    for t in sorted(set(vcpu_tiers)):
        placed = sum(of(v) <= t for v in task_vcpus)
        if placed != min(len(task_vcpus), sum(x <= t for x in vcpu_tiers)):
            return True
    return False


def cap_colors(per_color_views, free_counts, n_pages):
    """CAP's page-cache allocation, one interval per view: colors ranked
    hottest first by the view; the hottest is committed once it has led
    for ``HYSTERESIS`` views in a row, which restarts the cursor; pages
    come from the committed color first, then in rank order (unranked
    colors last, by number), rolling over to the next color with free
    pages and keeping the cursor there; every page is reclaimed at the
    interval's end.  ``free_counts`` is the pages free per color at the
    start of every interval.  Returns each interval's page colors in
    allocation order."""
    ranking = sorted(free_counts)
    committed = ranking[0] if ranking else None
    challenger, count, cursor = None, 0, 0
    out = []
    for rates in per_color_views:
        if rates:
            ranking = sorted(rates, key=rates.get, reverse=True)
            hottest = ranking[0]
            if hottest == committed:
                challenger, count = None, 0
            else:
                count = count + 1 if hottest == challenger else 1
                challenger = hottest
                if count >= HYSTERESIS:
                    committed, challenger, count, cursor = hottest, None, 0, 0
        order = [c for c in ranking if c in free_counts]
        order += sorted(c for c in free_counts if c not in order)
        if committed in order:
            order.remove(committed)
            order.insert(0, committed)
        free = dict(free_counts)
        colors = []
        for _ in range(n_pages):
            for step in range(len(order)):
                c = order[(cursor + step) % len(order)]
                if free[c] > 0:
                    cursor = (cursor + step) % len(order)
                    free[c] -= 1
                    colors.append(c)
                    break
        out.append(colors)
    return out
