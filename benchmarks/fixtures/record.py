#!/usr/bin/env python3
"""Records the small profiler trace the reduction's tests read.

Run on the chip, by hand, when the fixture has to be made anew:

    python3 benchmarks/fixtures/record.py <out-dir>

It runs the program's placement kernel three times at a small shape with
the device left idle before, between and after, under the same profiler
options and the same `bench.sync` annotation as run.py, and writes
`small.xplane.pb` and `small.json` (the host's monotonic stamps: the
sync point, each solve as a span, the end) into <out-dir>. The tests
hold `harness/xplane.py` to what these two files say together.
"""

import json
import shutil
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def main(out_dir: str) -> int:
    import jax
    import numpy as np

    from benchmarks.harness import xplane
    from nomad_tpu.scheduler.tpu import resolve_device
    from nomad_tpu.scheduler.tpu.kernels import solve_placement_compact

    device = resolve_device()
    n, g, maxc = 256, 8, 16
    cap = np.tile(np.array([4000, 8192, 102400], np.int32), (n, 1))
    args = (
        cap, np.zeros((n, 3), np.int32),
        np.tile(np.array([250, 128, 300], np.int32), (g, 1)),
        np.full(g, 12, np.int32),
        np.full((8, n // 8), 255, np.uint8), np.zeros(g, np.int32),
        np.zeros((8, n), np.float32), np.zeros(g, np.int32),
        np.full((8, n), 16, np.int16), np.zeros(g, np.int32),
    )
    jax.block_until_ready(solve_placement_compact(*args, max_count=maxc))
    out = Path(out_dir)
    tmp = out / "trace"
    shutil.rmtree(tmp, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp), profiler_options=options)
    stamps = {"device": device.to_wire(), "spans": []}
    stamps["sync_mono_ns"] = time.monotonic_ns()
    with jax.profiler.TraceAnnotation(xplane.SYNC_NAME):
        pass
    time.sleep(0.05)  # a stretch in which the device runs nothing
    for _ in range(3):
        t0 = time.monotonic_ns()
        jax.block_until_ready(solve_placement_compact(*args, max_count=maxc))
        stamps["spans"].append(["solve", t0, time.monotonic_ns()])
        time.sleep(0.02)
    stamps["end_mono_ns"] = time.monotonic_ns()
    jax.profiler.stop_trace()
    shutil.copy(xplane.find_trace(tmp), out / "small.xplane.pb")
    shutil.rmtree(tmp)
    (out / "small.json").write_text(json.dumps(stamps, indent=1) + "\n")
    print(json.dumps(stamps))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
