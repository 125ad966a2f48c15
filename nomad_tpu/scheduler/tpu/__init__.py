from .device import configure_compile_cache, resolve_device

# Before any kernel module loads: this package is the first solver-side
# jax import (the lazy seam in scheduler/__init__.py), so every jit
# below compiles against the one persistent cache directory.
configure_compile_cache()

from .kernels import (  # noqa: E402
    make_sharded_solver,
    make_sharded_solver_preempt,
    pad_g,
    pad_n,
    solve_placement,
)
from .lower import build_node_table, lower_group  # noqa: E402
from .sharding import SolverMesh, solver_mesh  # noqa: E402
from .scheduler import (  # noqa: E402
    PendingEvalBatch,
    TPUBatchScheduler,
    TPUGenericScheduler,
    solve_eval_batch,
    solve_eval_batch_begin,
)
from .solver import (  # noqa: E402
    BatchSolver,
    GroupAsk,
    PendingSolve,
    ResidentClusterState,
)
