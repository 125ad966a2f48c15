"""Host microsolve: the compact placement kernel in plain numpy.

Interactive-scale solves (a single `job register`, a handful of
placements) were paying the full tensor pipeline — lower, pad, upload,
device round-trip, readback — when the problem fits in a few cache
lines. Below the n·g microsolve threshold the solver runs THIS kernel
instead: the same waterfill math as kernels.solve_placement_compact
(same f32 ScoreFit, same stable score sort, same node-index-ordered
compact instance readback), executed synchronously on the host with
zero device round-trip and zero jit involvement. The dense path's
lowering, materialization, spread splits, overflow repair, and failure
accounting are all shared — only the kernel invocation differs — so a
micro solve is the dense solve, minus the device round-trip.

Not a third semantics: differential coverage pins this kernel to the
jax compact kernel's outcomes (tests/test_tpu_solver.py), the same way
the sharded kernels are pinned to the single-chip one.
"""

from __future__ import annotations

import numpy as np

# must match kernels.py exactly: scores are computed in f32 and ties
# break toward the lower node index (stable sort on -score)
NEG_INF = np.float32(-1e30)
LN10 = np.float32(2.302585092994046)
_BIG = np.int64(1 << 30)
_F1 = np.float32(1.0)
_F18 = np.float32(18.0)
_F20 = np.float32(20.0)


def solve_placement_compact_micro(
    cap: np.ndarray,
    used: np.ndarray,
    groups: list,
    max_count: int,
):
    """Place all groups on the host; mirror of solve_placement_compact.

    cap/used: [N, 3] integer (unpadded — the micro path never buckets);
    groups: [(ask [3] i64, count, feasible [N] bool, bias [N] f32,
    units_cap [N] i64)] in priority order. Returns
    (inst_node [G, max_count] i32 (-1 past each group's placed total),
    over [N] bool (always False — integer math cannot overflow),
    used' [N, 3] int64).
    """
    n = cap.shape[0]
    used = used.astype(np.int64, copy=True)
    cap = cap.astype(np.int64, copy=False)
    # group-invariant hoists: capacity never changes inside one solve
    safe_cap = np.maximum(cap.astype(np.float32), _F1)
    g = len(groups)
    inst = np.full((g, max_count), -1, dtype=np.int32)
    for gi, (ask, count, feas, bias, ucap) in enumerate(groups):
        count = int(count)
        if count <= 0:
            continue
        free = cap - used
        per_res = np.where(
            ask[None, :] > 0, free // np.maximum(ask[None, :], 1), _BIG
        )
        units = np.minimum(per_res.min(axis=1), ucap)
        units[~feas] = 0
        np.minimum(units, count, out=units)
        np.maximum(units, 0, out=units)
        if not units.any():
            continue
        # f32 ScoreFitBinPack — the kernel's formula term for term
        fr = _F1 - (used + ask[None, :]).astype(np.float32) / safe_cap
        total = np.exp(fr[:, 0] * LN10) + np.exp(fr[:, 1] * LN10)
        score = np.minimum(np.maximum(_F20 - total, 0.0), _F18) / _F18
        score = score + bias.astype(np.float32, copy=False)
        score[units <= 0] = NEG_INF
        order = np.argsort(-score, kind="stable")
        su = units[order]
        prior = np.cumsum(su) - su
        take_sorted = np.minimum(np.maximum(count - prior, 0), su)
        take = np.zeros(n, dtype=np.int64)
        take[order] = take_sorted
        used += take[:, None] * ask[None, :]
        placed_nodes = np.nonzero(take)[0]
        if placed_nodes.size:
            row = np.repeat(
                placed_nodes.astype(np.int32), take[placed_nodes]
            )[:max_count]
            inst[gi, : row.shape[0]] = row
    # the integer waterfill floors units from free capacity, so overflow
    # is impossible by construction — mirror the device kernel's
    # always-False defensive flags
    over = np.zeros(n, dtype=bool)
    return inst, over, used
