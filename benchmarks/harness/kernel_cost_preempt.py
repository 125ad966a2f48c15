"""Bytes the preemption kernel has to move, from the shapes alone.

`solve_placement_preempt` (scheduler/tpu/kernels.py) is a scan over the
groups of a solve. One step, for one group, over N nodes and 3
resources, must at least

  read   cap        [N, 3] i32  12 N    read   used_exist [N, 3] i32  12 N
  read   freed      [N, 3] i32  12 N    read   used_new   [N, 3] i32  12 N
  read   feasibility [N] bool      N    read   bias  [N] f32           4 N
  read   unit caps  [N] i32      4 N
  write  used_new'  [N, 3] i32  12 N    write  freed' [N, 3] i32      12 N
  write  take       [N] i32      4 N    write  take on victims [N] i32 4 N

= 89 N bytes, and read one row of the tier prefix ([N, 3] i32, 12 N) for
every tier the group may take victims from. Counted from the algorithm,
not from the implementation: that the program makes one waterfill pass
a tier and reads its carry again in each, its padded lanes, padded
groups and padded tiers are the program's waste, and count against it.
"""

STEP_BYTES_PER_NODE = 57 + 32
TIER_BYTES_PER_NODE = 12


def preempt_solve_bytes(nodes: int, groups: int, tiers: int) -> int:
    """Least bytes for one solve of `groups` groups over `nodes` nodes,
    each of which may take victims from `tiers` priority tiers."""
    return (STEP_BYTES_PER_NODE + TIER_BYTES_PER_NODE * tiers) \
        * nodes * groups


def preemptible_tiers(config: dict, delta_default: int = 10) -> int:
    """How many priority tiers the deployment's highest job class may
    take victims from: the distinct class priorities at least
    `priority_delta` under it."""
    classes = config.get("job_classes") or {}
    prios = sorted({int(c.get("priority", 50)) for c in classes.values()})
    if not prios:
        return 0
    delta = int(config.get("preemption", {}).get(
        "priority_delta", delta_default))
    return sum(1 for p in prios if prios[-1] - p >= delta)
