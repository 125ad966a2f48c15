"""Telemetry tests: registry primitives, histogram bucket/percentile
math, windowed-ring bounds, the Prometheus exposition validated by a
scraper-side parser, the /v1/metrics surface fed by the live server,
the e2e eval-latency acceptance gate, and the metric-name catalogue
checks (docs/metrics.md). Reference: command/agent/command.go:979
setupTelemetry, nomad/server.go:444-450 broker/plan-queue gauges."""

import math
import os
import re
import threading
import time

import pytest

from nomad_tpu import metrics, mock
from nomad_tpu.metrics import Registry

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def wait_until(fn, timeout_s=15.0, interval=0.05):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if fn():
            return True
        time.sleep(interval)
    return False


def test_registry_primitives():
    r = Registry()
    r.incr("a")
    r.incr("a", 2)
    r.set_gauge("g", 7)
    r.observe("lat", 0.5)
    r.observe("lat", 1.5)
    snap = r.snapshot()
    assert snap["counters"]["a"] == 3
    assert snap["gauges"]["g"] == 7
    s = snap["samples"]["lat"]
    assert s["count"] == 2 and s["min"] == 0.5 and s["max"] == 1.5
    assert s["mean"] == 1.0


def test_registry_provider_sampled_at_snapshot():
    r = Registry()
    live = {"depth": 0}
    r.register_provider("q", lambda: dict(live))
    live["depth"] = 9
    assert r.snapshot()["gauges"]["q.depth"] == 9
    r.unregister_provider("q")
    assert "q.depth" not in r.snapshot()["gauges"]


def test_registry_provider_errors_do_not_break_snapshot():
    r = Registry()
    r.register_provider("bad", lambda: 1 / 0)
    snap = r.snapshot()
    assert snap["gauges"]["bad.error"] == 1


def test_registry_threadsafe_observe():
    r = Registry()

    def hammer():
        for _ in range(2000):
            r.observe("x", 1.0)
            r.incr("c")

    ts = [threading.Thread(target=hammer) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    snap = r.snapshot()
    assert snap["samples"]["x"]["count"] == 8000
    assert snap["counters"]["c"] == 8000


def test_server_publishes_metrics_end_to_end(tmp_path):
    """Scheduling work shows up in /v1/metrics: broker gauges, worker
    invoke latency, and (with the TPU worker) solver timings."""
    from nomad_tpu.agent import Agent, AgentConfig
    from nomad_tpu.api.client import NomadClient

    cfg = AgentConfig()
    cfg.server_enabled = True
    cfg.client_enabled = False
    cfg.dev_mode = True
    cfg.http_port = 0
    cfg.data_dir = str(tmp_path)
    agent = Agent(cfg)
    agent.start()
    try:
        srv = agent.server.server
        for _ in range(3):
            srv.node_register(mock.node())
        job = mock.job()
        srv.job_register(job)
        assert srv.wait_for_evals(10)

        api = NomadClient(f"http://127.0.0.1:{agent.http_addr[1]}")
        snap = api.agent.metrics()
        assert snap["uptime_seconds"] >= 0
        gauges = snap["gauges"]
        assert "nomad.broker.total_ready" in gauges
        assert "nomad.plan_queue.depth" in gauges
        samples = snap["samples"]
        svc = samples.get("nomad.worker.invoke_seconds.service")
        assert svc and svc["count"] >= 1
    finally:
        agent.shutdown()


def test_tpu_solver_records_timings():
    from nomad_tpu.scheduler.tpu import solve_eval_batch
    from nomad_tpu.testing import Harness

    before = metrics.snapshot()["samples"].get(
        "nomad.tpu.solve_seconds", {"count": 0}
    )["count"]
    h = Harness()
    for _ in range(4):
        h.state.upsert_node(h.next_index(), mock.node())
    job = mock.job()
    h.state.upsert_job(h.next_index(), job)
    plans = solve_eval_batch(h.snapshot(), h, [mock.eval_for_job(job)])
    h.submit_plan(plans[next(iter(plans))])
    after = metrics.snapshot()["samples"]["nomad.tpu.solve_seconds"]["count"]
    assert after == before + 1


# ---------------------------------------------------------------------------
# Histogram bucket / percentile math
# ---------------------------------------------------------------------------


def test_histogram_percentile_math():
    r = Registry()
    for v in [0.01] * 50 + [0.1] * 40 + [1.0] * 10:
        r.observe("lat", v)
    s = r.snapshot()["samples"]["lat"]
    assert s["count"] == 100 and s["min"] == 0.01 and s["max"] == 1.0
    # bucket interpolation lands within one sqrt(2) bucket of the exact
    # quantile (p50 -> 0.01-region, p90 -> 0.1-region, p95/p99 -> the
    # 1.0 spike)
    assert 0.005 <= s["p50"] <= 0.016, s["p50"]
    assert 0.07 <= s["p90"] <= 0.15, s["p90"]
    assert 0.5 <= s["p95"] <= 1.0, s["p95"]
    assert 0.8 <= s["p99"] <= 1.0, s["p99"]
    assert s["p50"] <= s["p90"] <= s["p95"] <= s["p99"]


def test_histogram_single_value_clamps():
    """A degenerate distribution (all observations identical) must
    report that value for every quantile — the open-ended buckets clamp
    to observed min/max instead of reporting bucket edges."""
    r = Registry()
    for _ in range(100):
        r.observe("x", 0.25)
    s = r.snapshot()["samples"]["x"]
    for q in ("p50", "p90", "p95", "p99"):
        assert abs(s[q] - 0.25) < 1e-9, (q, s[q])


def test_histogram_empty_and_out_of_range():
    from nomad_tpu.metrics import DEFAULT_BOUNDS

    r = Registry()
    # above the top bound: lands in +Inf bucket, quantiles clamp to max
    r.observe("huge", DEFAULT_BOUNDS[-1] * 10)
    s = r.snapshot()["samples"]["huge"]
    assert s["p99"] == pytest.approx(DEFAULT_BOUNDS[-1] * 10)
    # below the bottom bound: first bucket, clamps to min
    r.observe("tiny", 1e-9)
    s = r.snapshot()["samples"]["tiny"]
    assert s["p50"] == pytest.approx(1e-9)


def test_windowed_ring_eviction_bounds():
    """The per-interval ring is hard-bounded and the last window
    reflects only recent observations — 'slow now' vs 'slow once'."""
    r = Registry(interval_s=0.01, ring=4)
    for i in range(40):
        r.observe("x", 0.001)
        time.sleep(0.012)
    h = r._hists["x"]
    assert len(h.ring) <= 4
    # rotated entries hold disjoint counts summing (with the live
    # interval) to <= the cumulative count
    ring_total = sum(e[3] for e in h.ring)
    assert ring_total + h.cur_count <= h.count == 40

    r2 = Registry(interval_s=0.05, ring=6)
    for _ in range(100):
        r2.observe("y", 0.001)
    time.sleep(0.06)
    for _ in range(10):
        r2.observe("y", 1.0)
    s = r2.snapshot()["samples"]["y"]
    assert s["count"] == 110
    w = s["window"]
    assert w["count"] == 10
    assert w["p50"] > 0.5, "window must see only the recent slow burst"
    assert s["p50"] < 0.01, "cumulative still dominated by the fast 100"


def test_configure_windows_applies_to_new_histograms():
    r = Registry(interval_s=10.0, ring=6)
    r.configure_windows(interval_s=0.5, ring=2)
    r.observe("z", 0.1)
    h = r._hists["z"]
    assert h.interval_s == 0.5 and h.ring.maxlen == 2


# ---------------------------------------------------------------------------
# Prometheus exposition, validated scraper-side
# ---------------------------------------------------------------------------

_LINE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(?:\{([^}]*)\})?'
    r' (-?(?:[0-9.]+(?:e[-+]?[0-9]+)?|Inf)|NaN)$'
)


def _parse_prom(text: str):
    """Minimal scraper-side parser for text exposition 0.0.4: validates
    line syntax and returns ({name: type}, {name: [(labels, value)]})."""
    types: dict = {}
    series: dict = {}
    for line in text.strip().splitlines():
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split()
            types[name] = kind
            continue
        assert not line.startswith("#"), line
        m = _LINE_RE.match(line)
        assert m, f"unscrapeable line: {line!r}"
        name, labels_raw, val = m.groups()
        labels = {}
        if labels_raw:
            for part in labels_raw.split(","):
                k, v = part.split("=", 1)
                assert v.startswith('"') and v.endswith('"'), line
                labels[k] = v[1:-1]
        series.setdefault(name, []).append((labels, float(val)))
    return types, series


def _validate_histograms(types, series):
    """Scraper-side invariants for every TYPE <h> histogram: le labels
    parse and strictly increase, bucket counts are monotone, the +Inf
    bucket closes the series and equals _count, and _sum/_count give a
    mean inside [min, max]."""
    checked = 0
    for name, kind in types.items():
        if kind != "histogram":
            continue
        buckets = series.get(name + "_bucket")
        assert buckets, f"{name}: histogram without buckets"
        les = []
        counts = []
        for labels, value in buckets:
            assert set(labels) == {"le"}, labels
            les.append(float("inf") if labels["le"] == "+Inf"
                       else float(labels["le"]))
            counts.append(value)
        assert les == sorted(les) and len(set(les)) == len(les), (
            f"{name}: le labels not strictly increasing: {les}"
        )
        assert les[-1] == float("inf"), f"{name}: missing +Inf bucket"
        assert counts == sorted(counts), (
            f"{name}: bucket counts not monotone: {counts}"
        )
        (_, total), = series[name + "_count"]
        (_, total_sum), = series[name + "_sum"]
        assert counts[-1] == total, f"{name}: +Inf bucket != _count"
        if total:
            (_, vmin), = series[name + "_min"]
            (_, vmax), = series[name + "_max"]
            mean = total_sum / total
            assert vmin - 1e-12 <= mean <= vmax + 1e-12, (
                f"{name}: mean {mean} outside [{vmin}, {vmax}]"
            )
        checked += 1
    return checked


def test_prometheus_exposition_format():
    """/v1/metrics?format=prometheus emits the text exposition format a
    stock Prometheus scrapes (reference command/agent/command.go:979):
    counters as _total, gauges, and REAL histogram series — validated
    by the scraper-side parser above."""
    import urllib.request

    from nomad_tpu.agent.agent import Agent, AgentConfig

    metrics.incr("nomad.rpc.request", 3)
    metrics.set_gauge("nomad.broker.total_ready", 7)
    for v in (0.002, 0.25, 0.03, 1.5):
        metrics.observe("nomad.worker.invoke", v)
    agent = Agent(AgentConfig.dev())
    agent.start()
    try:
        host, port = agent.http_addr
        raw = urllib.request.urlopen(
            f"http://{host}:{port}/v1/metrics?format=prometheus", timeout=5
        )
        assert raw.headers["Content-Type"].startswith("text/plain")
        text = raw.read().decode()
    finally:
        agent.shutdown()

    assert "# TYPE nomad_rpc_request_total counter" in text
    assert re.search(r"^nomad_rpc_request_total \d+$", text, re.M)
    assert "# TYPE nomad_broker_total_ready gauge" in text
    assert "# TYPE nomad_worker_invoke histogram" in text
    assert re.search(r'^nomad_worker_invoke_bucket\{le="[0-9.]+"\} \d+$',
                     text, re.M)
    assert re.search(r"^nomad_worker_invoke_count \d+$", text, re.M)
    assert re.search(r"^nomad_worker_invoke_sum [\d.]+$", text, re.M)
    types, series = _parse_prom(text)
    # every series name traces back to a TYPE declaration
    for name in series:
        base = re.sub(r"_(bucket|sum|count|min|max|last)$", "", name)
        assert name in types or base in types, f"no TYPE for {name}"
    assert _validate_histograms(types, series) >= 1


def test_statsd_sink_pushes_deltas():
    import socket

    from nomad_tpu.metrics import Registry, StatsdSink

    reg = Registry()
    srv = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    srv.bind(("127.0.0.1", 0))
    srv.settimeout(5)
    port = srv.getsockname()[1]
    sink = StatsdSink(f"127.0.0.1:{port}", interval_s=999, reg=reg)
    try:
        reg.incr("a.count", 5)
        reg.set_gauge("b.depth", 2)
        sink.push_once()
        data = srv.recv(65535).decode()
        assert "a_count:5|c" in data
        assert "b_depth:2|g" in data
        # counters push DELTAS: unchanged counter is omitted next push
        reg.incr("a.count", 1)
        sink.push_once()
        data = srv.recv(65535).decode()
        assert "a_count:1|c" in data
    finally:
        sink.stop()
        srv.close()


def test_statsd_sink_forwards_timings():
    """Histogram observations ride to statsd as |ms timings (the raw
    values, drained from the bounded capture buffer — the daemon
    aggregates real observations, not re-bucketed approximations)."""
    import socket

    from nomad_tpu.metrics import Registry, StatsdSink

    reg = Registry()
    srv = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    srv.bind(("127.0.0.1", 0))
    srv.settimeout(5)
    sink = StatsdSink(
        f"127.0.0.1:{srv.getsockname()[1]}", interval_s=999, reg=reg
    )
    try:
        reg.observe("nomad.test.lat_seconds", 0.25)
        reg.observe("nomad.test.lat_seconds", 0.5)
        sink.push_once()
        data = srv.recv(65535).decode()
        assert "nomad_test_lat_seconds:250.000|ms" in data
        assert "nomad_test_lat_seconds:500.000|ms" in data
        # count/sum companions still ride as gauges
        assert "nomad_test_lat_seconds.count:2|g" in data
        # drained: a second push with no new observations sends no
        # timing lines for the name
        sink.push_once()
        data = srv.recv(65535).decode()
        assert "|ms" not in data
    finally:
        sink.stop()
        srv.close()


def test_timing_capture_bounded_and_per_consumer():
    reg = Registry()
    h1 = reg.enable_timing_capture(cap=8)
    h2 = reg.enable_timing_capture(cap=8)
    for i in range(100):
        reg.observe("x", 0.001)
    # each consumer sees its own (bounded) copy of the stream — two
    # sinks must not race one shared buffer's destructive drain
    assert len(reg.drain_timings(h1)["x"]) == 8
    assert len(reg.drain_timings(h2)["x"]) == 8
    assert reg._timings_dropped == 184
    # disabled consumers stop accruing (and stop paying) entirely
    reg.disable_timing_capture(h1)
    reg.disable_timing_capture(h2)
    reg.observe("x", 0.001)
    assert reg.drain_timings(h1) == {}
    assert not reg._timing_sinks


def test_window_ages_out_without_traffic():
    """A burst followed by silence must not present as 'slow now':
    reading the histogram rotates the stale live interval, so age_s
    reflects when the traffic actually stopped."""
    r = Registry(interval_s=0.05, ring=6)
    for _ in range(5):
        r.observe("x", 1.0)
    time.sleep(0.12)
    w = r.snapshot()["samples"]["x"]["window"]
    assert w["count"] == 5
    assert w["age_s"] > 0.05, w


def test_datadog_sink_tags():
    """DogStatsD sink decorates every line with constant tags
    (reference command/agent/command.go:1010)."""
    import socket

    from nomad_tpu import metrics as m
    from nomad_tpu.metrics import DatadogSink

    srv = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    srv.bind(("127.0.0.1", 0))
    srv.settimeout(5)
    m.incr("nomad.dd.test", 2)
    sink = DatadogSink(
        f"127.0.0.1:{srv.getsockname()[1]}", tags={"dc": "dc1"}
    )
    sink.push_once()
    data = srv.recv(65535).decode()
    srv.close()
    assert any(
        line.endswith("|#dc:dc1") for line in data.splitlines()
    ), data


# ---------------------------------------------------------------------------
# Throughput gate: histograms vs the pre-change sample path (smoke size)
# ---------------------------------------------------------------------------


HIST_OVERHEAD_SCRIPT = r"""
import json, random, time

from nomad_tpu import mock, metrics
from nomad_tpu.metrics import Registry
from nomad_tpu.scheduler.tpu import solve_eval_batch
from nomad_tpu.testing import build_cluster

h, jobs = build_cluster(10, 1, 10, False)  # 10 nodes, one job of 10
snap = h.snapshot()
evals = [mock.eval_for_job(j) for j in jobs]
solve_eval_batch(snap, h, evals)  # warm before either measured side


def once(hist: bool) -> float:
    reg = Registry(histograms=hist)
    old = metrics._install_registry(reg)
    try:
        # a BURST per sample: one smoke solve is ~3ms, too close to
        # timer/scheduler granularity to compare singly
        t0 = time.perf_counter()
        for _ in range(10):
            solve_eval_batch(snap, h, evals)
        return time.perf_counter() - t0
    finally:
        metrics._install_registry(old)


# randomized interleave, MINIMUM per side (the trace-overhead gate's
# proven recipe, tests/test_trace.py): background wakeups resonate with
# any fixed h,s,h,s order, and a load spike can only RAISE a side's
# samples, never lower its min — so the per-side minimum over the
# shuffled window is the contention-free estimate.
order = [False, True] * 16
random.shuffle(order)
best = {False: float("inf"), True: float("inf")}
for hist in order:
    best[hist] = min(best[hist], once(hist))
print(json.dumps({
    # >= 0.95 means histograms kept >= 0.95x the sample path's rate
    "ratio": best[False] / best[True],
    "sample_ms": best[False] * 1e3,
    "hist_ms": best[True] * 1e3,
}))
"""


def test_histogram_throughput_vs_sample_path_smoke():
    """Acceptance gate: smoke-size scheduling throughput with the
    histogram registry stays >= 0.95x the pre-change count/sum sample
    path (Registry(histograms=False), kept as the comparator). Measured
    in a CLEAN subprocess — inside the full suite, daemon threads left
    by earlier agent tests steal timeslices in patterns that correlate
    with iteration order and turn any in-process comparison into noise
    (same rationale as the tracing overhead gate)."""
    import json
    import subprocess
    import sys

    # Up to 3 attempts: box-load noise is ONE-SIDED for this gate (the
    # true overhead is ~0.1% — two observes per smoke solve — so a
    # spike can only fake a failure, and a quiet window cannot fake a
    # pass of a real >5% regression across repeated attempts).
    attempts = []
    for _ in range(3):
        proc = subprocess.run(
            [sys.executable, "-c", HIST_OVERHEAD_SCRIPT],
            capture_output=True,
            text=True,
            timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            cwd=REPO_ROOT,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        out = json.loads(proc.stdout.strip().splitlines()[-1])
        attempts.append(out["ratio"])
        if out["ratio"] >= 0.95:
            return
    pytest.fail(
        f"histogram-enabled smoke throughput < 0.95x sample path in "
        f"all attempts: {attempts}"
    )


# ---------------------------------------------------------------------------
# E2E acceptance: a real TPU-worker batch records eval-latency
# percentiles served by /v1/metrics and rendered by `operator top`
# ---------------------------------------------------------------------------


def test_e2e_eval_latency_histograms_acceptance(tmp_path, capsys):
    """Round-8 acceptance gate: a 12-eval c2m-shaped batch through the
    real TPU batch worker records p50/p95/p99 for
    nomad.eval.e2e_seconds — cumulative AND last window — served by
    /v1/metrics (JSON + prometheus histogram buckets) and rendered via
    `operator top`."""
    from types import SimpleNamespace

    from nomad_tpu.agent import Agent, AgentConfig
    from nomad_tpu.api.client import NomadClient
    from nomad_tpu.cli.main import cmd_operator_top
    from nomad_tpu.scheduler.context import SchedulerConfig
    from nomad_tpu.structs import Constraint, Spread
    from nomad_tpu.structs.node_class import compute_node_class

    # fresh registry so counts below are this batch's (the providers
    # and observes all route through the module-level conveniences)
    old = metrics._install_registry(Registry())
    cfg = AgentConfig(
        server_enabled=True,
        dev_mode=True,
        use_tpu_batch_worker=True,
        data_dir=str(tmp_path / "agent"),
    )
    agent = Agent(cfg)
    try:
        agent.start()
        srv = agent.server.server
        # dense-path sized batch: 12 jobs x 10 allocs = 120 requests,
        # past the small-batch threshold
        assert SchedulerConfig().small_batch_threshold < 120
        for i in range(16):
            n = mock.node()
            n.datacenter = ["dc1", "dc2"][i % 2]
            n.resources.cpu = 4000
            n.resources.memory_mb = 8192
            n.computed_class = compute_node_class(n)
            srv.node_register(n)
        jobs = []
        for j in range(12):
            job = mock.job(id=f"c2m-{j}")
            job.datacenters = ["dc1", "dc2"]
            tg = job.task_groups[0]
            tg.count = 10
            tg.tasks[0].resources.cpu = 100
            tg.tasks[0].resources.memory_mb = 64
            tg.tasks[0].resources.networks = []
            job.constraints.append(
                Constraint("${attr.kernel.name}", "linux", "=")
            )
            job.spreads = [
                Spread(attribute="${node.datacenter}", weight=50)
            ]
            jobs.append(job)
        for job in jobs:
            # register WITHOUT the auto-eval so the whole wave enqueues
            # atomically below — one broker lock hold, one batch
            srv.raft_apply("job_register", (job, None))
        evals = [mock.eval_for_job(job) for job in jobs]
        srv.eval_broker.enqueue_all(evals)

        def placed():
            return all(
                len(srv.state.allocs_by_job("default", j.id)) >= 10
                for j in jobs
            )

        assert wait_until(placed, 60), "batch never placed"
        # acks (where e2e is observed) follow the plan commit
        assert wait_until(
            lambda: (metrics.snapshot()["samples"]
                     .get("nomad.eval.e2e_seconds", {})
                     .get("count", 0)) >= 12,
            15,
        ), "e2e latency histogram never reached 12 observations"

        api = NomadClient(f"http://127.0.0.1:{agent.http_addr[1]}")
        snap = api.agent.metrics()
        e2e = snap["samples"]["nomad.eval.e2e_seconds"]
        assert e2e["count"] >= 12
        for q in ("p50", "p95", "p99"):
            assert e2e[q] > 0, (q, e2e)
        assert e2e["p50"] <= e2e["p95"] <= e2e["p99"]
        win = e2e["window"]
        assert win["count"] >= 12
        for q in ("p50", "p95", "p99"):
            assert win[q] > 0, (q, win)
        # labelled variant rides beside the aggregate
        assert any(
            k.startswith("nomad.eval.e2e_seconds.")
            for k in snap["samples"]
        )
        # the stage histograms the tentpole wired end to end
        for name in (
            "nomad.broker.wait_seconds",
            "nomad.plan_queue.wait_seconds",
            "nomad.plan.submit_seconds",
            "nomad.raft.apply_seconds",
            "nomad.tpu.batch_dispatch_seconds",
            "nomad.tpu.commit_seconds",
        ):
            assert snap["samples"].get(name, {}).get("count", 0) >= 1, name

        # prometheus: real buckets for the e2e histogram, and the whole
        # payload passes the scraper-side validator
        text = api.agent.metrics_prometheus()
        assert "# TYPE nomad_eval_e2e_seconds histogram" in text
        assert 'nomad_eval_e2e_seconds_bucket{le="+Inf"}' in text
        types, series = _parse_prom(text)
        assert _validate_histograms(types, series) >= 5

        # rendered via `operator top`
        args = SimpleNamespace(
            address=f"http://127.0.0.1:{agent.http_addr[1]}",
            token=None,
            region=None,
            interval=2.0,
            n=0,
            once=True,
        )
        capsys.readouterr()
        assert cmd_operator_top(args) == 0
        out = capsys.readouterr().out
        assert "nomad.eval.e2e_seconds" in out
        assert "WP99" in out and "P50" in out
        assert "Throughput" in out and "plan queue" in out
    finally:
        agent.shutdown()
        metrics._install_registry(old)


# ---------------------------------------------------------------------------
# Catalogue: emitted names ⊆ docs/metrics.md, statically and at runtime
# ---------------------------------------------------------------------------


def _catalogue_names() -> list:
    doc = open(os.path.join(REPO_ROOT, "docs", "metrics.md")).read()
    names = re.findall(r"^\| `([^`]+)` \|", doc, re.M)
    assert names, "docs/metrics.md catalogue table not found"
    return names


def _catalogue_regexes() -> list:
    out = []
    for name in _catalogue_names():
        rx = re.sub(r"<[^>]+>", ".+", re.escape(name))
        out.append(re.compile("^" + rx + "$"))
    return out


def _in_catalogue(name: str, regexes) -> bool:
    if name.endswith(".error"):
        return True  # provider-failure fallback gauge (metrics.py)
    return any(rx.match(name) for rx in regexes)


def test_runtime_metric_names_within_catalogue(tmp_path):
    """Drive a real server + HTTP round-trips on a fresh registry and
    assert every emitted counter/gauge/sample name matches the
    docs/metrics.md catalogue — a typo'd name at any call site that
    this workload reaches fails here."""
    from nomad_tpu.agent import Agent, AgentConfig
    from nomad_tpu.api.client import NomadClient

    regexes = _catalogue_regexes()
    old = metrics._install_registry(Registry())
    cfg = AgentConfig()
    cfg.server_enabled = True
    cfg.client_enabled = False
    cfg.dev_mode = True
    cfg.http_port = 0
    cfg.data_dir = str(tmp_path)
    agent = Agent(cfg)
    try:
        agent.start()
        srv = agent.server.server
        for _ in range(3):
            srv.node_register(mock.node())
        srv.job_register(mock.job())
        assert srv.wait_for_evals(15)
        api = NomadClient(f"http://127.0.0.1:{agent.http_addr[1]}")
        api.jobs.list()
        api.agent.metrics()
        snap = api.agent.metrics()
    finally:
        agent.shutdown()
        metrics._install_registry(old)
    emitted = (
        list(snap["counters"]) + list(snap["gauges"])
        + list(snap["samples"])
    )
    unknown = [n for n in emitted if not _in_catalogue(n, regexes)]
    assert unknown == [], (
        f"metric names emitted but not in docs/metrics.md: {unknown}"
    )


_CALLSITE_RE = re.compile(
    r"metrics\.(incr|observe|set_gauge|time_ns|register_provider)\(\s*"
    r'(f?)"([^"]+)"',
    re.S,
)


def _canonical(name: str) -> str:
    """Collapse runtime-label placeholders ({expr} at call sites,
    <label> in the catalogue) to a sentinel for comparison."""
    return re.sub(r"(\{[^}]*\}|<[^>]+>)", "※", name)


def test_static_call_site_names_in_catalogue():
    """Tooling tripwire: walk the source for metrics.incr/observe/
    set_gauge/time_ns/register_provider call sites with literal names
    and assert each appears in the docs/metrics.md catalogue — a typo'd
    metric name fails CI without needing a workload to reach it."""
    names = _catalogue_names()
    raw = set(names)
    canon = [_canonical(n) for n in names]
    pkg = os.path.join(REPO_ROOT, "nomad_tpu")
    misses = []
    for dirpath, _dirs, files in os.walk(pkg):
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            src = open(path).read()
            for m in _CALLSITE_RE.finditer(src):
                kind, is_f, name = m.group(1), m.group(2), m.group(3)
                rel = os.path.relpath(path, REPO_ROOT)
                if kind == "register_provider":
                    # provider prefixes publish <prefix>.<suffix> gauges
                    if not any(r.startswith(name + ".") for r in raw):
                        misses.append(f"{rel}: provider {name!r}")
                    continue
                if not is_f:
                    if name not in raw:
                        misses.append(f"{rel}: {name!r}")
                    continue
                c = _canonical(name)
                # an f-string may be the PREFIX of a multi-literal
                # concatenation (adjacent string literals), so prefix
                # matching against the catalogue is the correct check
                if not any(
                    cat == c or cat.startswith(c) for cat in canon
                ):
                    misses.append(f"{rel}: f-string {name!r}")
    assert misses == [], (
        "metric call sites missing from docs/metrics.md:\n  "
        + "\n  ".join(misses)
    )
