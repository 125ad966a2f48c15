#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

ONE process, which holds the chip. It starts one dev server agent with
the TPU batch worker (the `-tpu-scheduler` construction path, as
chip_smoke.py does; no chip is an error, there is no CPU fallback),
registers the configuration's fleet over `Node.register` and its standing
load over the front door, warms the cell's own shapes, measures for
`--seconds`, drains what it started, holds the store to the reference
rules the configuration's guarantees name, and prints the result as ONE
JSON line, last on stdout. `--trace 0` reports the cell's end-to-end
metrics, `--trace 1` its per-layer metrics from the program's spans and
counters and a profiler trace of the window. Failures by cause go to
stderr and to `benchmarks/out/<cell>.<seed>.json`.

`--rehearsal` is the only way onto the CPU: tiny sizes, JAX_PLATFORMS
pinned to cpu before jax loads, and no device metric (idle share, kernel
time, roofline) under any name. It serves the tests.

Exit code 0 and a result line, or non-zero and no result line.
"""

from __future__ import annotations

import time
from collections import Counter

T_PROCESS = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
if str(BENCH_DIR.parent) not in sys.path:
    sys.path.insert(0, str(BENCH_DIR.parent))

from benchmarks.harness import spec as spec_mod  # noqa: E402

REHEARSAL_NODES = 256
DEVICE_SOURCES = ("device_trace",)  # never reported from a CPU run


class BenchFailure(Exception):
    """The run cannot give a result; the message is the reason."""


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearsal", action="store_true",
                   help="tiny run on XLA:CPU (pins JAX_PLATFORMS=cpu)")
    p.add_argument("--bench-dir", default=str(BENCH_DIR),
                   help="another copy of benchmarks/ (the tests' use)")
    return p.parse_args(argv)


def run(args, t_process: float) -> dict:
    bench_dir = Path(args.bench_dir).resolve()
    try:
        cell = spec_mod.load_cell(args.workload, bench_dir)
    except (spec_mod.SpecError, KeyError) as e:
        raise BenchFailure(f"cannot load the cell: {e}") from e
    seconds = args.seconds
    if seconds is None:
        seconds = float(spec_mod.load_json(
            bench_dir.parent / "BENCHMARK.json")["run_seconds"])
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        from nomad_tpu import faultplane
        from nomad_tpu.scheduler.tpu import resolve_device
    except ImportError as e:
        raise BenchFailure(
            f"the nomad_tpu package is not importable from here: {e}") from e
    try:
        device = resolve_device()
    except RuntimeError as e:
        raise BenchFailure(f"no TPU: {e}") from e
    if not args.rehearsal and device.platform != "tpu":
        raise BenchFailure(
            f"no TPU: jax resolved to {device.platform!r}; pass --rehearsal "
            "for the tiny XLA:CPU run")
    if not args.rehearsal and device.count < cell.chips:
        raise BenchFailure(f"the cell asks for {cell.chips} chips and jax "
                           f"sees {device.count}")
    knobs = faultplane.env_knobs_active()
    if knobs:
        raise BenchFailure(f"fault-injection knobs are live: {knobs}")

    import jax

    from benchmarks.harness import jobs, ops, probe, warm
    from benchmarks.harness.cluster import Cluster, SetupFailure
    from benchmarks.harness.observer import Observer
    from benchmarks.reference import density
    from benchmarks.reference.snapshot import snapshot

    probe.install_compile_listener()
    params = dict(cell.traffic)
    n_nodes = int(cell.config["nodes"])
    if args.rehearsal:
        params.update(params.get("rehearsal", {}))
        n_nodes = REHEARSAL_NODES
    generator = cell.generator()
    rules = cell.rules()
    may_remain = tuple(cell.config.get("may_remain", ()))
    fits = [density.allocs_per_node(node, jc["ask"])
            for node in spec_mod.node_classes(cell.config)
            for jc in spec_mod.job_classes(cell.config).values()]
    # the arithmetic ideal of `packing_share` is true only where every
    # node and every ask is the same
    per_node = fits[0] if len(fits) == 1 else None
    if per_node is not None and \
            per_node != int(cell.config["allocs_per_node"]):
        raise BenchFailure(
            f"the configuration says {cell.config['allocs_per_node']} allocs "
            f"fit a node; its node and ask give {per_node}")
    least_fit = min((f for f in fits if f > 0), default=1)

    cluster = Cluster(cell.config, n_nodes, args.seed)
    observer = None
    trace_dir = bench_dir / "out" / f"trace.{cell.name}.{args.seed}"
    pr = probe.Probe(traced=bool(args.trace))
    profiling = False
    report: dict = {"workload": cell.name, "seed": args.seed,
                    "seconds": seconds, "trace": args.trace,
                    "rehearsal": args.rehearsal}
    try:
        try:
            cluster.start()
        except SetupFailure as e:
            raise BenchFailure(f"set-up: {e}") from e
        server = cluster.server
        t_registered = time.monotonic()
        observer = Observer(server.state, server.watch_hub,
                            hang_s=ops.DRAIN_DEADLINE_S)
        ctx = ops.RunContext(config=cell.config, params=params,
                             seed=args.seed, seconds=seconds,
                             http=cluster.http, observer=observer)

        # -- what runs on the cluster before the window ---------------
        for op, body in _standing_load(cell.config, cluster.fleet.nodes, ctx):
            ctx.send(op, body)
            if op.acked:
                ctx.await_visible(op)
            if not op.watch.done.is_set():
                raise BenchFailure(
                    f"set-up: the standing job {op.job_id} did not become "
                    f"visible ({op.visible} of {op.asked} allocs, status "
                    f"{op.status})")
        t_standing = time.monotonic()

        # -- warm the cell's own shapes -------------------------------
        shapes = generator.shapes(params, cell.config)
        n_dry = warm.dry_solves(server, cell.config, shapes)
        dcs = len(cell.config["datacenters"])
        max_rows = min(n_nodes, 2 * max(
            s["evals"] * (-(-s["count"] // least_fit) + dcs) for s in shapes))
        buckets = warm.scatter_buckets(server, max_rows)
        for i, w in enumerate(generator.warm_jobs(params)):
            # a count, or (count, job_class, priority)
            count, job_class, priority = (w, None, None) \
                if isinstance(w, int) else w
            if priority is None:
                priority = int(params["priority"])
            job = jobs.make_job(cell.config, f"warm-{args.seed}-{i}", count,
                                priority, job_class)
            op = ctx.new_op(job.id, count, "warm", job_class)
            ctx.send(op, jobs.encode(job))
            if op.acked:
                ctx.await_visible(op)
        left = ops.settle(cluster, ctx, may_remain=may_remain)
        if left:
            raise BenchFailure(f"set-up: the warm-up deploys did not settle: "
                               f"{left}")
        setup_ops, ctx.ops = ctx.ops, []
        t_warm = time.monotonic()

        # -- the window ----------------------------------------------
        sync = {}

        def on_open() -> None:
            nonlocal profiling
            if args.trace and not args.rehearsal:
                from benchmarks.harness import xplane

                shutil.rmtree(trace_dir, ignore_errors=True)
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = 1
                jax.profiler.start_trace(str(trace_dir),
                                         profiler_options=options)
                profiling = True
                sync["mono_ns"] = time.monotonic_ns()
                with jax.profiler.TraceAnnotation(xplane.SYNC_NAME):
                    pass
            pr.open()
            report["setup_s"] = time.monotonic() - t_process

        ctx.on_open = on_open
        generator.run(ctx)
        t_close = time.monotonic()
        sync["close_mono_ns"] = time.monotonic_ns()
        pr.close()
        if profiling:
            jax.profiler.stop_trace()
            profiling = False

        # -- drain, then judge ---------------------------------------
        left = ops.settle(cluster, ctx, may_remain=may_remain)
        causes = ops.judge(ctx, server.state)
        snap = snapshot(server.state,
                        {"never_visible": observer.never_visible})
        expected = {op.job_id: (op.asked, op.ask)
                    for op in setup_ops + ctx.ops
                    if op.acked and op.watch.done.is_set()}
        faults_by_rule = {name: rule.check(snap, expected, cell.config)
                          for name, rule in rules}
        faults = [f for said in faults_by_rule.values() for f in said]
        pack = density.packing(snap, per_node) if per_node else None
        mem = (jax.devices()[0].memory_stats() or {})
        counters = pr.samples["counters"]
    finally:
        if profiling:
            jax.profiler.stop_trace()
        if observer is not None:
            observer.stop()
        cluster.stop()

    # -- metrics ------------------------------------------------------
    sent = [op for op in ctx.ops if op.sent]
    window = {"open_s": ctx.t_open - t_process,
              "close_s": t_close - t_process}
    client = {
        "register_s": [op.t_acked - op.t_sent for op in sent],
        "e2e_s": [op.watch.t_visible - op.t_sent for op in sent
                  if op.watch.done.is_set()],
        "fanout_s": observer.fanout_s,
    }
    values: dict[str, float] = {"setup_s": report["setup_s"]}
    n_failed_ops, attempted = _account(sent, faults, observer, seconds,
                                       values)
    if pack is not None and pack["packing_share"] is not None:
        values["packing_share"] = pack["packing_share"]
        if pack["packing_share"] > 100.0 + 1e-9:
            faults.append(
                f"packing_share {pack['packing_share']:.3f} is over 100: the "
                f"placement touches {pack['nodes_touched']} nodes and the "
                f"reference says {pack['ideal_nodes']} is the least — the "
                "reference is wrong about what fits a node")
    compiles = pr.samples["compiles"]
    on_chip = device.platform == "tpu"
    correct = (not faults and not compiles and (on_chip or args.rehearsal)
               and n_failed_ops == 0 and not left)
    # every number `correct` compares, beside its limit
    checks = {f"faults.{name}": {"value": len(said), "limit": 0}
              for name, said in faults_by_rule.items()}
    checks["failed"] = {"value": n_failed_ops, "limit": 0}
    checks["compiles_in_window"] = {"value": len(compiles), "limit": 0}
    checks["left_in_flight"] = {"value": sum(left.values()), "limit": 0}
    if "packing_share" in values:
        checks["packing_share"] = {"value": values["packing_share"],
                                   "limit": 100.0}

    samples = dict(pr.samples, client=client)
    samples["derived"] = {"kernel_path_s": _kernel_path_s(samples["batches"])}
    device_out = dict(device.to_wire(),
                      memory_peak_bytes=mem.get("peak_bytes_in_use"))
    breakdown = None
    if args.trace and on_chip:
        needles = {m["file"]["module"] for m in cell.per_layer
                   if "module" in m["file"]}
        breakdown = _read_trace(trace_dir, sync, needles, samples,
                                device_out, report)
        shutil.rmtree(trace_dir, ignore_errors=True)

    red_ctx = {"config": cell.config, "device_kind": device.device_kind}
    metrics_out = {}
    if args.trace:
        for m in cell.per_layer:
            if m["source"] in DEVICE_SOURCES and not on_chip:
                continue  # a CPU second is no device second
            reducer = spec_mod.load_module(
                "reducers", m["file"]["reducer"], bench_dir)
            v = reducer.reduce(samples, m["file"], red_ctx)
            if v is not None:
                metrics_out[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics_out[m["name"]] = {"value": values[m["name"]],
                                          "unit": m["unit"]}
    if args.rehearsal:
        # a rehearsal prints no time figure: an XLA:CPU second is not a
        # second of the system, under any name
        metrics_out = {k: v for k, v in metrics_out.items()
                       if v["unit"] not in ("ms", "s", "allocs/s")}

    report.update(
        correct=correct, attempted=attempted, failed=n_failed_ops,
        failures_by_cause=causes, store_faults=faults, not_settled=left,
        compiles_in_window=[list(c) for c in compiles],
        watched_counters={k: counters.get(k, 0)
                          for k in probe.WATCHED_COUNTERS},
        plans_trimmed=sum(1 for b in samples["batches"]
                          if b["status"] == "partial") if args.trace else None,
        ops_completed_by_more_than_one_commit=sum(
            1 for op in sent
            if op.kind != "job" and op.watch.commits > 1),
        duplicate_alloc_ids_seen=observer.duplicate_ids,
        fleet_errors=cluster.fleet.errors, packing=pack, window=window,
        setup={"registered_s": t_registered - t_process,
               "standing_s": t_standing - t_process,
               "standing_jobs": sum(op.kind == "standing"
                                    for op in setup_ops),
               "warmed_s": t_warm - t_process, "dry_solves": n_dry,
               "scatter_buckets": buckets},
        ops={"sent": len(sent),
             "by_kind": dict(Counter(op.kind for op in sent))},
        path_counts={k: len(pr.samples["timings"].get(n, ()))
                     for k, n in (("kernel", "nomad.tpu.device_seconds"),
                                  ("micro", "nomad.tpu.micro_seconds"),
                                  ("host_stack",
                                   "nomad.tpu.small_batch_requests"))}
        if args.trace else None,
        host_threads=pr.samples["host"],
        batches_in_window=pr.samples["batches_in_window"],
        batch_evals=[b["evals"] for b in samples["batches"]]
        if args.trace else None,
        # (seconds since the window opened, allocs visible): one point a
        # routed commit, thinned to 200 — how a backlog drained
        visible_timeline=observer.timeline[::max(
            1, len(observer.timeline) // 200)] + observer.timeline[-1:],
        max_batch_evals=max(pr.samples["timings"].get(
            "nomad.tpu.batch_evals", [0])) if args.trace else None,
        device=device_out, metrics=metrics_out,
    )
    if on_chip:
        report["times"] = _time_stats(sent, samples["spans"], ctx.t_open,
                                      seconds)
    line = {"correct": correct, "attempted": attempted,
            "failed": n_failed_ops, "metrics": metrics_out,
            "device": device_out}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks  # last in the line
    report["line"] = line
    return report


def _standing_load(config: dict, fleet_nodes: list, ctx):
    """The configuration's `standing` entries as (op, body), in order:
    `jobs` jobs of `count` allocs of a job class at a priority, or as
    many as fill `fill_share` of what the fleet holds of that class's
    ask. Sent one after another through the front door, each waited for,
    so the cluster is the same for every seed."""
    from benchmarks.harness import jobs
    from benchmarks.reference import density

    for k, entry in enumerate(config.get("standing", ())):
        job_class, count = entry.get("job_class"), int(entry["count"])
        n_jobs = entry.get("jobs")
        if n_jobs is None:
            ask = spec_mod.job_class(config, job_class)["ask"]
            room = sum(density.allocs_per_node(
                {"cpu_mhz": n.resources.cpu, "memory_mb": n.resources.memory_mb,
                 "disk_mb": n.resources.disk_mb}, ask) for n in fleet_nodes)
            n_jobs = int(float(entry["fill_share"]) * room) // count
        for i in range(int(n_jobs)):
            job = jobs.make_job(config, f"standing-{ctx.seed}-{k}-{i}", count,
                                entry.get("priority"), job_class)
            yield (ctx.new_op(job.id, count, "standing", job_class),
                   jobs.encode(job))


def _account(sent, faults, observer, seconds, values):
    """`attempted`, `failed` and the traffic's own end-to-end metrics.

    Jobs sent as kind "job" are a backlog: one operation is one alloc of
    a job whose PUT was acked, attempted once it reached an outcome
    (visible inside the window, or failed). Anything else is a deploy:
    one operation each, attempted when sent inside the window, waited
    for past its end."""
    from benchmarks.harness.series import quantile

    failed_ops = 0
    backlog = [op for op in sent if op.kind == "job"]
    deploys = [op for op in sent if op.kind != "job"]
    attempted = 0
    if backlog:
        for op in backlog:
            if op.failed:
                failed_ops += op.asked - op.visible
        # visible inside the window: the timeline's last point at or
        # before its end; the rate runs to the earlier of that end and
        # the last alloc becoming visible
        inside = [(t, n) for t, n in observer.timeline if t <= seconds]
        if inside:
            t_last, n_vis = inside[-1]
            all_asked = sum(op.asked for op in backlog if op.acked)
            span = t_last if n_vis >= all_asked else seconds
            values["placements_per_s"] = n_vis / span
            attempted += n_vis
        attempted += failed_ops
    if deploys:
        attempted += len(deploys)
        failed_ops += sum(1 for op in deploys if op.failed)
        lat = [op.watch.t_visible - op.t_sent for op in deploys
               if not op.failed and op.watch.done.is_set()]
        if lat:
            values["e2e_p50_ms"] = 1e3 * quantile(lat, 0.50)
            values["e2e_p95_ms"] = 1e3 * quantile(lat, 0.95)
    if faults:
        # a broken invariant of the store is a failed run whatever the
        # count says; it is at least one failed operation
        failed_ops = max(failed_ops, 1)
        attempted = max(attempted, failed_ops)
    return failed_ops, attempted


def _time_stats(sent, spans: dict, t_open: float, seconds: float) -> dict:
    """For the report file of a chip run: where the latency sits, by the
    kind of deploy, by the third of the window it was sent in (does it
    drift?), and by span."""
    from benchmarks.harness.series import quantile

    def stats(xs) -> dict:
        xs = list(xs)
        if not xs:
            return {"n": 0}
        return {"n": len(xs), "p50": quantile(xs, 0.5),
                "p95": quantile(xs, 0.95), "max": max(xs),
                "sum": sum(xs)}

    done = [op for op in sent if op.watch.done.is_set()]

    def e2e(ops_) -> dict:
        return stats(1e3 * (op.watch.t_visible - op.t_sent) for op in ops_)

    return {
        "e2e_ms_by_kind": {k: e2e([op for op in done if op.kind == k])
                           for k in sorted({op.kind for op in done})},
        "e2e_ms_by_third_of_window": [
            e2e([op for op in done if op.kind != "job"
                 and i <= 3 * (op.t_sent - t_open) / seconds < i + 1])
            for i in range(3)],
        "span_ms": {name: stats((e - s) / 1e6 for s, e in rows)
                    for name, rows in sorted(spans.items())},
    }


def _kernel_path_s(batches: list) -> list[float]:
    """For every batch that waited on the device: dispatch start to the
    end of the readback, in seconds."""
    out = []
    for b in batches:
        by = {}
        for name, start, end in b["spans"]:
            by.setdefault(name, []).append((start, end))
        if "device.wait" in by and "solve.dispatch" in by and "readback" in by:
            out.append((max(e for _, e in by["readback"])
                        - min(s for s, _ in by["solve.dispatch"])) / 1e9)
    return out


def _read_trace(trace_dir, sync, needles, samples, device_out,
                report) -> dict:
    """Reduce the profiler's trace: fills samples["device"] (with the
    executions of the modules the cell's metrics name, `needles`), the
    busy and window seconds of `device`, and returns the breakdown."""
    from benchmarks.harness import xplane

    trace = xplane.read(xplane.find_trace(trace_dir))
    if trace["sync_ns"] is None:
        raise BenchFailure("the profiler's trace holds no "
                           f"{xplane.SYNC_NAME} annotation")
    if not trace["devices"]:
        raise BenchFailure(f"the trace has no device plane: {trace['planes']}")
    offset = trace["sync_ns"] - sync["mono_ns"]  # monotonic -> trace clock
    t0 = trace["sync_ns"]
    t1 = sync["close_mono_ns"] + offset
    b = xplane.busy(trace, t0, t1)
    samples["device"] = {
        "idle_share": b["idle_share"], "busy_s": b["busy_s"],
        "window_s": b["window_s"],
        "modules": {n: xplane.module_seconds(trace, t0, t1, n)
                    for n in needles},
    }
    device_out["busy_s"] = b["busy_s"]
    device_out["window_s"] = b["window_s"]
    host_spans = [(name, s + offset, e + offset)
                  for name, rows in samples["spans"].items()
                  if name not in ("eval", "tpu.batch", "http", "broker.wait")
                  for s, e in rows]
    gaps = xplane.idle_gaps(trace, t0, t1, host_spans)
    report["trace"] = {
        "planes": trace["planes"],
        "device_lines": {n: d["lines"] for n, d in trace["devices"].items()},
        "module_seconds": {
            n: sum(d) for dev in trace["devices"].values()
            for n, d in xplane.by_name(dev["modules"], t0, t1).items()},
        "per_device_busy_s": b["per_device"],
    }
    return {"device_ops": [[n, s] for n, s in xplane.top_ops(trace, t0, t1)],
            "idle_gaps": [[n, s] for n, s in gaps]}


def main(argv=None, t_process: float = None) -> int:
    args = parse(argv)
    logging.basicConfig(
        level=logging.WARNING, stream=sys.stderr,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    try:
        report = run(args, T_PROCESS if t_process is None else t_process)
    except BenchFailure as e:
        print(f"benchmarks/run.py: FAIL: {e}", file=sys.stderr)
        return 2
    line = report.pop("line")
    out = Path(args.bench_dir).resolve() / "out" / \
        f"{args.workload}.{args.seed}.json"
    try:
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(report, indent=1, default=str) + "\n")
    except OSError as e:
        print(f"benchmarks/run.py: cannot write {out}: {e}", file=sys.stderr)
    summary = {k: report[k] for k in (
        "workload", "seed", "correct", "attempted", "failed",
        "failures_by_cause", "store_faults", "not_settled",
        "compiles_in_window", "watched_counters", "plans_trimmed",
        "ops_completed_by_more_than_one_commit", "packing", "setup", "ops",
        "path_counts", "batches_in_window")}
    print(f"benchmarks/run.py: {json.dumps(summary, default=str)}",
          file=sys.stderr)
    for name, c in line["checks"].items():
        print(f"benchmarks/run.py: check {name} {c['value']} limit "
              f"{c['limit']}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
