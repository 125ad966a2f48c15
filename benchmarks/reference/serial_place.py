"""A plain serial placer: what a run of small placements one after
another should give, written without the solver.

The microsolve's documented rule (`nomad_tpu/scheduler/tpu/microsolve.py`,
the compact kernel's in numpy), applied to one ask at a time, each on
the usage the ones before it left:

- a node takes at most as many instances of an ask as its free room
  holds in every resource the ask names (integer division; a resource
  the ask does not name bounds nothing), and no more than the count;
- nodes are ranked by ScoreFitBinPack of the node WITH one instance
  added: `10 ** (1 - used/cap)` summed over cpu and memory, taken from
  20, clipped to [0, 18] and divided by 18, in 32-bit floats; a node
  that takes none ranks last;
- the best node takes all it can, then the next, until the count is
  placed; ties go to the lower node index.

Two batches solved beside each other — the second chained on the
first while the first's commit is pending — have to give what this
gives for the two asks in order.
"""

from __future__ import annotations

import numpy as np

_F32 = np.float32


def _score(cap_row, used_row, ask) -> np.float32:
    """ScoreFitBinPack of one node with one instance of `ask` added."""
    total = _F32(0.0)
    for r in (0, 1):
        fr = _F32(1.0) - _F32(used_row[r] + ask[r]) / max(_F32(cap_row[r]),
                                                          _F32(1.0))
        total = _F32(total + _F32(np.exp(_F32(fr * _F32(np.log(10.0))))))
    return _F32(min(max(_F32(20.0) - total, _F32(0.0)), _F32(18.0))
                / _F32(18.0))


def _fits(cap_row, used_row, ask, count: int) -> int:
    units = count
    for r in range(3):
        if ask[r] > 0:
            units = min(units, (cap_row[r] - used_row[r]) // ask[r])
    return max(int(units), 0)


def place(cap, used, asks) -> tuple[list[list[int]], list[list[int]]]:
    """cap, used: one [cpu, mem, disk] row a node. asks: (ask, count) in
    the order they are placed. Returns, for every ask, the node index of
    each instance placed (ascending), and the usage after the last."""
    cap = [[int(v) for v in row] for row in cap]
    used = [[int(v) for v in row] for row in used]
    placed = []
    for ask, count in asks:
        ask = [int(v) for v in ask]
        units = [_fits(cap[i], used[i], ask, int(count))
                 for i in range(len(cap))]
        ranked = sorted(
            (i for i in range(len(cap)) if units[i] > 0),
            key=lambda i: (-float(_score(cap[i], used[i], ask)), i),
        )
        left, nodes = int(count), []
        for i in ranked:
            if left == 0:
                break
            take = min(units[i], left)
            nodes += [i] * take
            used[i] = [used[i][r] + take * ask[r] for r in range(3)]
            left -= take
        placed.append(sorted(nodes))
    return placed, used
