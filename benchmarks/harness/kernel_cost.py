"""Bytes the placement kernel has to move, from the shapes alone.

`solve_placement_compact` (scheduler/tpu/kernels.py) is a scan over the
groups of a batch. One step, for one group, over N nodes and 3
resources, must at least

  read   cap  [N, 3] i32   12 N      read   used [N, 3] i32   12 N
  read   feasibility [N] bool  N     read   bias [N] f32       4 N
  read   unit caps [N] i16   2 N     write  used' [N, 3] i32  12 N
  write  take [N] i32        4 N

= 47 N bytes; compacting the result then reads the step's take row once
more (4 N). The instance list it writes ([max_count] i32 per group) is
under 1 % of that and is left out, as are padded lanes and padded
groups: they are the program's waste, and count against it.
"""

STEP_BYTES_PER_NODE = 47 + 4


def compact_solve_bytes(nodes: int, groups: int) -> int:
    """Least bytes for one solve of `groups` groups over `nodes` nodes."""
    return STEP_BYTES_PER_NODE * nodes * groups
