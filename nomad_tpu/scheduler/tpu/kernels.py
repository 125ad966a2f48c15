"""JAX placement kernels: vectorized ScoreFit + capacity waterfill.

The deliberate architectural departure from the reference: instead of the
per-alloc iterator chain (reference: scheduler/rank.go BinPackIterator.Next
:193 scoring one node at a time, scheduler/stack.go limiting to log2(n)
candidates), a whole batch of task groups is placed in one compiled program:

  for each group g (lax.scan, priority order):
      units[n]  = how many instances of g fit on node n     (int division)
      score[n]  = normalized bin-pack ScoreFit + bias        (vectorized)
      place `count_g` instances onto the best-scored nodes   (sort + cumsum)
      node_used += placed * ask_g

One scan step places an entire group — the sequential best-fit greedy the
reference runs per alloc collapses into a waterfall over the score-sorted
node axis, because filling the currently-best node until it stops being
best is exactly what per-instance best-fit does.

All shapes are padded to buckets (pad_n/pad_g) so XLA compiles once per
bucket, not once per cluster size. Scores use the reference formula
(structs/funcs.go:237): score = 20 - 10^freeCpu - 10^freeMem, normalized
to [0,1]; bias (affinity/spread) is added on top.

Multi-chip: `make_sharded_solver` shards the node axis over a mesh with
shard_map. Per scan step the per-node score/units vectors are all-gathered
(2 x N x 4B per group — rides ICI), the waterfill decision is computed
replicated, and each device applies its slice of the placement.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NUM_RES = 3
# Plain Python float: a module-level jnp scalar would eagerly initialize the
# JAX backend at import time (the lazy-import seam in scheduler/__init__
# promises the control plane never pays that unless the backend is selected).
NEG_INF = -1e30
LN10 = 2.302585092994046


def _pad_to(x: int, bucket: int) -> int:
    return ((x + bucket - 1) // bucket) * bucket


def _shard_map(body, mesh: Mesh, in_specs, out_specs):
    """jax.shard_map with the replication check disabled: the waterfill
    decision is computed replicated from all-gathered vectors, which the
    checker cannot prove."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def jit_cache_sizes() -> dict[str, int]:
    """The jit-cache entry count of each module-level kernel, straight
    from jax — the ground truth the compile ledger (solverobs.py) is
    cross-checked against in /v1/solver/status. Our signature ledger
    COUNTS events over time; this reports what jax currently CACHES, so
    ledger compiles >= cache size always holds (evictions, restarts).
    Entry-point factories (make_sharded_solver*) build fresh jits per
    mesh and are observed per-instance by their callers instead."""
    out: dict[str, int] = {}
    for name, fn in (
        ("solve_placement", solve_placement),
        ("solve_placement_compact", solve_placement_compact),
        ("solve_placement_preempt", solve_placement_preempt),
    ):
        try:
            out[name] = int(fn._cache_size())
        except Exception:  # private API seam: absent ⇒ report unknown
            out[name] = -1
    return out


def pad_n(n: int) -> int:
    """Node-axis bucket: powers of two up to 2048, then multiples of 2048.

    Power-of-two buckets alone waste up to ~2x work at the top (10k nodes
    padded to 16384 is 64% dead lanes on every scan step); 2048-granular
    buckets cap the waste at <20% while staying multiples of 8 devices x
    128 lanes for the sharded solver and the VPU alike. Recompiles happen
    once per bucket and amortize across the server's lifetime exactly as
    before.
    """
    size = 256
    while size < n and size < 2048:
        size *= 2
    if n <= size:
        return size
    return _pad_to(n, 2048)


# The compact solve's ladder (PR 32). XLA compiles one program for each
# static shape it meets, 3.2-4.8 s apiece on a v5e, and a warm-up can
# only finish a set that is closed and listed: so the group axis and the
# readback width take their padded sizes from these two ladders and from
# nowhere else. Both climb by 4, because a rung's waste is device time
# and the device has it to spare (my chip runs, PR 32, at 14,336 nodes:
# a padded scan step, count 0, takes ~27 us and a real one ~80 us; the
# compaction ~11 ns a group x readback slot, 11 ms at 256 x 4,096; a
# batch's host side is 400 ms and more), while every program is seconds
# of set-up on an empty cache: 16 programs a node bucket, where rungs
# that doubled would be 30. G_LADDER's top is what a worker's batch
# reaches, 64 evals of a job spread over four values; C_LADDER starts at
# 64 because a batch the kernel sees holds 49 requests or more.
G_LADDER = (8, 32, 128, 256)
C_LADDER = (64, 256, 1024, 4096)


def pad_g(g: int) -> int:
    """Group-axis bucket: the least rung of G_LADDER that holds `g`
    (8, 32, 128, 256). Past the top the ladder goes on in multiples of
    256, so any batch still solves and its program still follows from
    the group count alone; such a program is outside compact_programs(),
    compiles on first use and is counted by
    `nomad.tpu.compact.programs_new`. (Finer rungs buy nothing: with a
    rung every 8 groups a batch of mixed jobs met a program no warm-up
    had listed in most windows, ~4 s each.)"""
    for rung in G_LADDER:
        if g <= rung:
            return rung
    return _pad_to(g, G_LADDER[-1])


def pad_c(c: int) -> int:
    """Instance-count bucket for the compact readback: the least rung of
    C_LADDER that holds `c` (64, 256, 1,024, 4,096), then x 4 past the
    top (outside compact_programs(), as pad_g's overflow)."""
    for rung in C_LADDER:
        if c <= rung:
            return rung
    size = C_LADDER[-1]
    while size < c:
        size *= 4
    return size


def compact_programs() -> list[tuple[int, int]]:
    """Every (group bucket, instance bucket) the compact solve compiles
    for the batches a worker drains, in order: the closed set a warm-up
    has to finish, for each node bucket. Which of them a batch lands in
    follows from its group count (pad_g) and from its largest group
    (pad_c of the readback bound, which is at most the largest count)
    and from nothing else — the row tables' sizes and the unit caps'
    dtype follow from the two rungs (solver._compact_dispatch) — so a
    batch of ONE job class at one count reaches every program a mixed
    batch can."""
    return [(gp, maxc) for gp in G_LADDER for maxc in C_LADDER]


def pad_t(t: int) -> int:
    """Tier-axis bucket of the preempt solve's prefix: room for `t`
    priority tiers and the all-zero row before them, in multiples of 4
    (4 holds three tiers), so that the program does not follow the
    number of distinct priorities a cluster happens to hold."""
    return max(4, _pad_to(t + 1, 4))


def preempt_programs() -> list[tuple[int, int]]:
    """Every (group bucket, tier bucket) the preempt solve compiles for
    the batches a worker drains on a cluster of up to three priority
    tiers, in order: the closed set a warm-up has to finish, for each
    node bucket, as compact_programs() is the compact solve's. The
    dense [G, N] result has no readback rung, so a batch's program
    follows from its group count (pad_g) and the cluster's tiers
    (pad_t) alone; the retry of a spread's leftovers runs the same
    kernel with every tier limit 0. A cluster of more tiers lands on the
    next tier bucket, outside this list, and is counted by
    `nomad.tpu.preempt.programs_new`."""
    return [(gp, pad_t(3)) for gp in G_LADDER]


def _score_nodes(cap_f, used_f, ask_f, bias_g):
    """Vectorized ScoreFitBinPack after hypothetically adding one instance.

    cap_f/used_f: [N, R] f32; ask_f: [R] f32; bias_g: [N] f32 -> [N] f32.
    Mirrors structs/funcs.go:237 on the cpu/mem dimensions.
    """
    util = used_f + ask_f[None, :]
    safe_cap = jnp.maximum(cap_f, 1.0)
    free = 1.0 - util / safe_cap  # [N, R]
    total = jnp.exp(free[:, 0] * LN10) + jnp.exp(free[:, 1] * LN10)
    score = jnp.clip(20.0 - total, 0.0, 18.0) / 18.0
    return score + bias_g


def _units_for(free, ask, ucap, feas_g, count):
    """How many instances fit per node given free capacity + caps."""
    per_res = jnp.where(
        ask[None, :] > 0,
        free // jnp.maximum(ask[None, :], 1),
        jnp.int32(1 << 30),
    )
    units = jnp.min(per_res, axis=1)  # [N]
    units = jnp.clip(units, 0, ucap)
    units = jnp.where(feas_g, units, 0)
    # Clip to the group's count: keeps the cumsum far from int32 overflow
    # and changes nothing (a node can never take more than count instances).
    return jnp.clip(units, 0, count)


def _waterfill(score, units, count):
    """Fill the score-sorted node axis until `count` instances placed."""
    order = jnp.argsort(-score)  # best first
    su = units[order]
    prior = jnp.cumsum(su) - su
    take_sorted = jnp.clip(count - prior, 0, su)
    return jnp.zeros_like(units).at[order].set(take_sorted)


def _waterfill_topk(score, units, count, k: int):
    """_waterfill restricted to the k best-scored nodes — exact when k
    bounds the nodes the full fill could touch.

    Every node the full waterfill takes from receives >= 1 instance, so
    the receiving set is at most min(count, sum(units)) nodes, and those
    are by construction the highest-scored unit-bearing nodes
    (unit-less nodes carry NEG_INF). The caller passes k = the compact
    readback width, which already upper-bounds min(count, placeable) for
    every group in the batch (solver._run_compact derives it from free
    capacity before the scan, and free only shrinks as groups place), so
    the top-k fill places the same counts as the full sort — and the
    same NODES wherever top_k breaks ties lower-index-first like the
    stable argsort of -score, which is checked on XLA:CPU (the
    differential tests) and only reported on the chip (chip_smoke.py
    `parity`; any tie order is a valid placement). A full [N] sort per
    scan step was the single largest cost of the compact kernel on
    XLA:CPU (~4.5x); what it costs on the chip is not measured.
    """
    _, order = lax.top_k(score, k)
    su = units[order]
    prior = jnp.cumsum(su) - su
    take_sorted = jnp.clip(count - prior, 0, su)
    return jnp.zeros_like(units).at[order].set(take_sorted)


def _place_group(cap, carry, xs, fill=_waterfill):
    """One lax.scan step: place count_g instances of one group. `fill`
    picks the waterfill variant (full sort, or top-k where the caller can
    bound the receiving node set)."""
    used = carry
    ask, count, feas_g, bias_g, ucap = xs
    units = _units_for(cap - used, ask, ucap, feas_g, count)
    score = _score_nodes(cap.astype(jnp.float32), used.astype(jnp.float32),
                         ask.astype(jnp.float32), bias_g)
    score = jnp.where(units > 0, score, NEG_INF)
    take = fill(score, units, count)
    used = used + take[:, None] * ask[None, :]
    return used, take


@functools.partial(jax.jit, static_argnames=())
def solve_placement(cap, used, asks, counts, feas, bias, units_cap):
    """Place all groups.

    cap, used: [N, R] i32; asks: [G, R] i32; counts: [G] i32;
    feas: [G, N] bool; bias: [G, N] f32; units_cap: [G, N] i32.
    Returns (assign [G, N] i32, used' [N, R] i32).
    """
    step = functools.partial(_place_group, cap)
    used, takes = lax.scan(step, used, (asks, counts, feas, bias, units_cap))
    return takes, used


@functools.partial(jax.jit, static_argnames=("max_count",))
def solve_placement_compact(
    cap,
    used,
    asks,
    counts,
    feas_packed,
    feas_idx,
    bias_rows,
    bias_idx,
    ucap_rows,
    ucap_idx,
    *,
    max_count: int,
):
    """solve_placement with compressed transfers in BOTH directions.

    The host<->TPU link (PCIe/DCN) is the slow resource at c2m scale,
    not the MXU: the dense [G, N] f32/i32 inputs are
    ~60 MB and the [G, N] result another 20 MB. Three reductions:

      * input dedupe — groups lowered from the same job share identical
        bias/units-cap/feasibility rows (spread sub-groups reference the
        parent's arrays; unconstrained jobs are all-equal). The host sends
        row tables + a per-group row index; the kernel gathers on device.
      * feasibility rows travel bit-packed ([G, N/8] u8, unpacked once on
        device); unit caps travel as i16 (caps beyond the group count are
        equivalent to it) wherever max_count fits it.
      * compact result — instead of [G, N] counts, the device emits the
        node index of each placed instance ([G, max_count] i32 via
        searchsorted over the per-group cumsum), plus [N] overflow flags.

    The program is a function of (N, G, max_count) and nothing else
    (PR 32): G and max_count are rungs of the ladder (pad_g, pad_c;
    compact_programs() lists them), each row table has G rows whatever
    the batch's distinct rows are — an all-equal batch fills one, a
    batch in which every group differs fills all — and the unit caps'
    dtype follows from max_count. Padding is inert: a padded group has
    count 0 and places nothing, a padded row is never indexed, and the
    host cuts the readback to the real groups.

    The overflow flags are a defensive invariant check, not an expected
    path: the integer waterfill can never place past free capacity (units
    are floor-divided from it), so `over` is always all-False from this
    kernel. If it ever fires (a future kernel bug, a miscomputed `used`
    input), the host re-verifies flagged nodes with exact integer math
    instead of silently committing an overcommit.

    Returns (inst_node [G, max_count] i32 (-1 past each group's placed
    total), over [N] bool, used' [N, R] i32).
    """
    n = cap.shape[0]
    feas_rows = jnp.unpackbits(feas_packed, axis=1, count=n).astype(bool)

    # top-k waterfill: max_count bounds every group's receiving node set
    # (see _waterfill_topk), so the partial fill is exact; k > N
    # degenerates to the full sort (top-N = every node)
    k = min(max_count, n)

    def step(used_c, xs):
        ask, count, fi, bi, ui = xs
        # gather the group's deduped rows, then the shared scan step
        return _place_group(
            cap,
            used_c,
            (ask, count, feas_rows[fi], bias_rows[bi],
             ucap_rows[ui].astype(jnp.int32)),
            fill=lambda s, u, c: _waterfill_topk(s, u, c, k),
        )

    used_out, takes = lax.scan(
        step, used, (asks, counts, feas_idx, bias_idx, ucap_idx)
    )

    cum = jnp.cumsum(takes, axis=1)  # [G, N]
    idx = jnp.arange(max_count, dtype=jnp.int32)

    def compact_one(cum_g):
        # compare_all: one pass of N x max_count compares, which the
        # vector unit streams; the default binary search is max_count
        # chains of log2(N) dependent gathers (the same integers either
        # way; on a v5e at 14,336 nodes, 256 groups x 4,096: 11 ms of
        # the kernel's 32 against 150 of 171, my chip runs, PR 32)
        node = jnp.searchsorted(
            cum_g, idx, side="right", method="compare_all"
        ).astype(jnp.int32)
        return jnp.where(idx < cum_g[-1], node, -1)

    inst_node = jax.vmap(compact_one)(cum)
    placed_res = used_out - used
    over = jnp.any(placed_res > jnp.maximum(cap - used, 0), axis=1)
    return inst_node, over, used_out


# ---------------------------------------------------------------------------
# Preemption-aware variant: per-priority-tier usage tensors
# ---------------------------------------------------------------------------


def _place_group_preempt(cap, used_exist, prefix_used, carry, xs,
                         fill=_waterfill, total=jnp.sum):
    """One scan step of the preemption kernel: a waterfill pass for each
    row of the tier prefix (reference analog: generic_sched.go:773
    selectNextOption's run-again-with-preemption + preemption.go's
    lowest-priority-first candidate order, tensorized):

      pass 0: normal waterfill against remaining real capacity
        (prefix_used[0] is all-zero);
      pass k >= 1: the still unplaced remainder retries with capacity
        EXPANDED by the usage of the k LOWEST priority tiers, as long as
        the group may preempt that many (`klim`: tiers at least
        PRIORITY_DELTA below the group's job priority). A pass finishes
        over the whole cluster before the next one opens, so a tier is
        touched only once every lower one is spent wherever the group
        can go — the reference takes victims lowest priority first
        (Preemptor) and prefers nodes whose victims are of lower
        priority (PreemptionScoringIterator); pooling all tiers in one
        pass made every full node tie and the victims' tiers arbitrary.

    The carry tracks `freed` — preemptible usage already claimed, by
    earlier passes and by earlier (higher-priority) groups in this batch
    — so two groups can never double-spend the same victim capacity; on
    a node it stands for the lowest tiers first, which is the order the
    host picks exact victims in. Placements of the passes that may evict
    are returned separately: the host picks victim allocs per node and
    emits plan.node_preemptions.

    `fill` and `total` are the waterfill and the sum over the node axis:
    the node-sharded variant passes its all-gathered fill and a psum, so
    both run THIS math.
    """
    used_new, freed = carry
    ask, count, feas_g, bias_g, ucap, klim = xs
    cap_f = cap.astype(jnp.float32)
    ask_f = ask.astype(jnp.float32)

    def tier_pass(state, tier):
        used_new, freed, taken, evicting, remaining = state
        k, prefix_k = tier
        used_total = used_exist - freed + used_new  # what still stands
        normal_free = cap - used_total
        preemptible = jnp.where(
            k <= klim, jnp.maximum(prefix_k - freed, 0), 0
        )  # [N, R]
        units = _units_for(
            normal_free + preemptible, ask, ucap - taken, feas_g, remaining
        )
        score = _score_nodes(
            cap_f,
            jnp.maximum(used_total - preemptible, 0).astype(jnp.float32),
            ask_f,
            bias_g,
        )
        score = jnp.where(units > 0, score, NEG_INF)
        take = fill(score, units, remaining)
        claimed = take[:, None] * ask[None, :]
        # how much of the pass eats into victims (vs leftover free)
        overflow = jnp.maximum(claimed - jnp.maximum(normal_free, 0), 0)
        return (
            used_new + claimed,
            freed + jnp.minimum(overflow, preemptible),
            taken + take,
            evicting + jnp.where(k > 0, take, 0),
            remaining - total(take),
        ), None

    zeros = jnp.zeros_like(ucap)
    tiers = jnp.arange(prefix_used.shape[0], dtype=klim.dtype)
    (used_new, freed, taken, evicting, _), _ = lax.scan(
        tier_pass, (used_new, freed, zeros, zeros, count),
        (tiers, prefix_used),
    )
    return (used_new, freed), (taken, evicting)


@jax.jit
def solve_placement_preempt(
    cap, used_exist, prefix_used, asks, counts, feas, bias, units_cap, tier_limit
):
    """Place all groups with preemption tiers.

    cap, used_exist: [N, R] i32; prefix_used: [T+1, N, R] i32 cumulative
    usage of the T priority tiers (ascending priority; prefix_used[k] =
    usage of the k lowest tiers); tier_limit: [G] i32 — how many tiers
    each group may preempt (0 = none). Returns
    (assign [G, N], assign_evict [G, N], used' [N, R]).
    """
    n = cap.shape[0]
    zeros = jnp.zeros((n, cap.shape[1]), dtype=cap.dtype)
    step = functools.partial(_place_group_preempt, cap, used_exist, prefix_used)
    (used_new, freed), (takes, takes_evict) = lax.scan(
        step, (zeros, zeros), (asks, counts, feas, bias, units_cap, tier_limit)
    )
    return takes, takes_evict, used_exist - freed + used_new


# ---------------------------------------------------------------------------
# Sharded variant: node axis split over a device mesh
# ---------------------------------------------------------------------------


def _sharded_waterfill(score_loc, units_loc, count, axis, my, n_local):
    """Replicated waterfill decision from node-sharded score/unit vectors.

    All-gathers the [N/D] local vectors to the full [N] (identical on every
    device — the decision is deterministic and replicated), fills in score
    order, and returns this device's slice of the take vector. The gathered
    vectors are exactly the unsharded kernel's, so placements match the
    single-chip solver bit for bit.
    """
    score = lax.all_gather(score_loc, axis, tiled=True)  # [N]
    units = lax.all_gather(units_loc, axis, tiled=True)  # [N]
    order = jnp.argsort(-score)
    su = units[order]
    prior = jnp.cumsum(su) - su
    take_sorted = jnp.clip(count - prior, 0, su)
    take = jnp.zeros_like(units).at[order].set(take_sorted)
    return lax.dynamic_slice(take, (my * n_local,), (n_local,))


def _topk_fill(score_loc, units_loc, count, axis, my, n_local, k: int):
    """Distributed waterfill whose per-device cost shrinks with the mesh.

    The replicated variant above all-gathers the FULL [N] vectors and
    argsorts them on EVERY device — O(N log N) per device no matter how
    many devices share the work, which is exactly the term that stops a
    node-sharded solve from scaling. This variant keeps per-device work
    ∝ the shard:

      1. each device top-k's its LOCAL [N/D] score slice — O(N/D log k);
      2. the D×k candidate (score, units, global-index) triples are
         all-gathered — O(D·k) bytes over ICI, independent of N;
      3. the waterfill runs replicated over the tiny candidate set —
         O(D·k log D·k), independent of N;
      4. each device keeps its own slice of the take vector.

    Exact vs the full sort whenever k >= min(count, n_local) for every
    group (the caller guarantees it — solver-side the readback-width
    bound already upper-bounds any group's receiving set): the full
    waterfill's receiving set is a prefix of the global score order with
    at most `count` members (each receives >= 1 instance), so every
    receiving node — and every node ranked above one — survives its
    shard's local top-k, and the candidate cumsum reproduces the full
    sort's priors bit for bit. Tie order matches argsort's
    lower-global-index-first: candidates are pre-sorted by global index,
    then stably argsorted by -score.

    Returns (take [n_local], candidate global indices [D*k] in
    waterfill order, candidate takes [D*k]); the candidate arrays are
    replicated on every device — the compact emission
    (_candidates_to_inst) reads them directly."""
    sv, si = lax.top_k(score_loc, k)  # local best-k (ties: lower idx)
    su = units_loc[si]
    gidx = (si + my * n_local).astype(jnp.int32)
    vs = lax.all_gather(sv, axis, tiled=True)  # [D*k]
    us = lax.all_gather(su, axis, tiled=True)
    gs = lax.all_gather(gidx, axis, tiled=True)
    o0 = jnp.argsort(gs)  # global-index order first ...
    order = jnp.argsort(-vs[o0])  # ... so stable -score sort ties by it
    su_s = us[o0][order]
    prior = jnp.cumsum(su_s) - su_s
    take_sorted = jnp.clip(count - prior, 0, su_s)
    gs_s = gs[o0][order]
    loc = gs_s - my * n_local
    mine = (loc >= 0) & (loc < n_local)
    take = (
        jnp.zeros((n_local + 1,), units_loc.dtype)
        .at[jnp.where(mine, loc, n_local)]
        .add(jnp.where(mine, take_sorted, 0))
    )
    return take[:n_local], gs_s, take_sorted


def _candidates_to_inst(gs_s, take_sorted, maxc: int):
    """Compact per-instance node list from the replicated candidate set:
    exactly solve_placement_compact's readback (instances enumerated in
    node-index order, -1 past the placed total) — but computed over the
    D*k candidates instead of the full [N] take vector, so the compact
    emission costs O(D*k log D*k) replicated, independent of N.
    Non-candidate nodes all have take 0, and searchsorted(side=right)
    skips zero-take entries, so the candidate-compressed cumsum yields
    the identical instance sequence."""
    o2 = jnp.argsort(gs_s)  # node-index order, matching compact_one
    gs2 = gs_s[o2]
    cum = jnp.cumsum(take_sorted[o2])
    idxv = jnp.arange(maxc, dtype=jnp.int32)
    pos = jnp.searchsorted(cum, idxv, side="right")
    node = gs2[jnp.clip(pos, 0, gs2.shape[0] - 1)]
    return jnp.where(idxv < cum[-1], node, -1).astype(jnp.int32)


def make_sharded_solver_preempt(mesh: Mesh, axis: str = "nodes"):
    """Node-sharded variant of solve_placement_preempt.

    Same contract: (cap, used_exist, prefix_used, asks, counts, feas, bias,
    units_cap, tier_limit) -> (assign [G,N], assign_evict [G,N], used').
    The tier prefix tensors are sharded over the node axis alongside
    cap/used (each device owns its nodes' preemptible-capacity prefixes);
    per tier pass, only the [N] score and unit vectors ride ICI. The scan
    step IS _place_group_preempt, given the all-gathered waterfill and a
    psum, so single-chip and sharded solves are bit-equal
    (tests/test_tpu_solver.py).
    """

    def sharded_solve(
        cap, used_exist, prefix_used, asks, counts, feas, bias, units_cap,
        tier_limit,
    ):
        def body(cap_l, usede_l, prefix_l, asks_l, counts_l, feas_l, bias_l,
                 ucap_l, tl_l):
            my = lax.axis_index(axis)
            n_local = cap_l.shape[0]

            step = functools.partial(
                _place_group_preempt, cap_l, usede_l, prefix_l,
                # the decision is replicated from the all-gathered [N]
                # vectors; the remainder must be the GLOBAL one
                fill=lambda score, units, count: _sharded_waterfill(
                    score, units, count, axis, my, n_local
                ),
                total=lambda take: lax.psum(jnp.sum(take), axis),
            )

            zeros = jnp.zeros_like(cap_l)
            (used_new, freed), (takes, takes_evict) = lax.scan(
                step, (zeros, zeros),
                (asks_l, counts_l, feas_l, bias_l, ucap_l, tl_l),
            )
            return takes, takes_evict, usede_l - freed + used_new

        return _shard_map(
            body,
            mesh,
            in_specs=(
                P(axis, None),        # cap
                P(axis, None),        # used_exist
                P(None, axis, None),  # prefix_used [T+1, N, R]
                P(),                  # asks
                P(),                  # counts
                P(None, axis),        # feas
                P(None, axis),        # bias
                P(None, axis),        # units_cap
                P(),                  # tier_limit
            ),
            out_specs=(P(None, axis), P(None, axis), P(axis, None)),
        )(cap, used_exist, prefix_used, asks, counts, feas, bias, units_cap,
          tier_limit)

    sharded_solve.__name__ = f"sharded_solver_preempt_d{mesh.shape[axis]}"
    return jax.jit(sharded_solve)


def make_sharded_solver(mesh: Mesh, axis: str = "nodes",
                        max_count: int | None = None,
                        compact: bool = False):
    """Build a pjit'd solver with the node axis sharded over `mesh`.

    Scoring/feasibility/unit math runs on each device's node shard. The
    waterfill decision depends on max_count:

      * None (default, the always-exact reference form): the full [N]
        score and unit vectors are all-gathered per scan step and the
        replicated decision argsorts them — O(G * N * 8 bytes) over ICI
        but O(N log N) compute on EVERY device.
      * an int bounding every group's count: the distributed top-k
        waterfill (_sharded_waterfill_topk) — per-device compute shrinks
        with the mesh (O(N/D) local + O(D*k) replicated) and only the
        D*k candidate triples ride ICI. The production path
        (scheduler/tpu/sharding.py SolverMesh) derives the bound from
        the batch's group counts, bucketed for jit-signature stability.

    compact=True (requires max_count): instead of the dense [G, N]
    assignment, returns (inst_node [G, max_count] i32 replicated,
    over [N] bool, used' [N, R]) — the same readback contract as
    solve_placement_compact, emitted from the replicated candidate set
    so the device->host transfer is [G, maxC], never [G, N]. Bit-equal
    to the single-chip compact kernel (same waterfill, same node-order
    instance enumeration, `over` all-False by the same integer-capacity
    argument).
    """
    n_dev = mesh.shape[axis]
    if compact and max_count is None:
        raise ValueError("compact sharded solver requires max_count")

    def sharded_solve(cap, used, asks, counts, feas, bias, units_cap):
        def body(cap_l, used_l, asks_l, counts_l, feas_l, bias_l, ucap_l):
            # *_l node-sharded: cap_l [N/D, R]; feas_l [G, N/D]; asks/counts
            # replicated.
            my = lax.axis_index(axis)
            n_local = cap_l.shape[0]

            def step(used_loc, xs):
                ask, count, feas_g, bias_g, ucap = xs
                units_loc = _units_for(
                    cap_l - used_loc, ask, ucap, feas_g, count
                )
                score_loc = _score_nodes(
                    cap_l.astype(jnp.float32),
                    used_loc.astype(jnp.float32),
                    ask.astype(jnp.float32),
                    bias_g,
                )
                score_loc = jnp.where(units_loc > 0, score_loc, NEG_INF)
                if max_count is None:
                    take_loc = _sharded_waterfill(
                        score_loc, units_loc, count, axis, my, n_local
                    )
                    return (
                        used_loc + take_loc[:, None] * ask[None, :],
                        take_loc,
                    )
                take_loc, gs_s, take_sorted = _topk_fill(
                    score_loc, units_loc, count, axis, my, n_local,
                    min(max_count, n_local),
                )
                used_loc = used_loc + take_loc[:, None] * ask[None, :]
                if not compact:
                    return used_loc, take_loc
                inst = _candidates_to_inst(gs_s, take_sorted, max_count)
                return used_loc, inst

            used_out, per_group = lax.scan(
                step, used_l, (asks_l, counts_l, feas_l, bias_l, ucap_l)
            )
            if not compact:
                return per_group, used_out
            placed_res = used_out - used_l
            over_loc = jnp.any(
                placed_res > jnp.maximum(cap_l - used_l, 0), axis=1
            )
            return per_group, over_loc, used_out

        out_specs = (
            # inst is computed replicated (candidate math), over and
            # used' stay node-sharded
            (P(None, None), P(axis), P(axis, None))
            if compact
            else (P(None, axis), P(axis, None))
        )
        return _shard_map(
            body,
            mesh,
            in_specs=(
                P(axis, None),  # cap
                P(axis, None),  # used
                P(),  # asks
                P(),  # counts
                P(None, axis),  # feas
                P(None, axis),  # bias
                P(None, axis),  # units_cap
            ),
            out_specs=out_specs,
        )(cap, used, asks, counts, feas, bias, units_cap)

    # ledger identity: per-mesh compile entries are attributable to their
    # device count (the k bucket rides in the caller's signature tuple)
    sharded_solve.__name__ = (
        f"sharded_solver_compact_d{n_dev}" if compact
        else f"sharded_solver_d{n_dev}"
    )
    return jax.jit(sharded_solve)
