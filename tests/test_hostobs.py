"""Continuous host-profiling tests (nomad_tpu/hostobs.py): the
thread-CPU ledger (CPU by role from the kernel's per-thread clocks, wait
inside spans, threads shorter than a pass, the account closing against
the process's clock), sampler attribution units (role x span x
function, bounded ledgers), TimedLock
wait accounting + Condition compatibility, GC/runtime telemetry, the
/v1/profile/* surface + ACL battery + debug-bundle capture, the
single-flight guard on /v1/agent/pprof/profile, profiler/trace teardown
across Agent.reload and shutdown (no sampler thread leaks,
stop-during-inflight-capture), the e2e acceptance batch through the
real TPUBatchWorker, and the profiled-vs-unprofiled throughput gate
(clean-subprocess minima, the round-10 methodology)."""

import gc
import json
import os
import threading
import time
from types import SimpleNamespace

import pytest

from nomad_tpu import hostobs, metrics, mock, trace
from nomad_tpu.hostobs import HostProfiler, TimedLock
from nomad_tpu.metrics import Registry

pytestmark = pytest.mark.profile

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def wait_until(fn, timeout_s=15.0, interval=0.05):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if fn():
            return True
        time.sleep(interval)
    return False


def _profiler_threads():
    return [t for t in threading.enumerate() if t.name == "host-profiler"]


# ---------------------------------------------------------------------------
# TimedLock: wait attribution + Condition compatibility
# ---------------------------------------------------------------------------


def test_timed_lock_uncontended_is_free():
    lk = TimedLock("unit_uncontended", threading.Lock())
    for _ in range(100):
        with lk:
            pass
    assert lk.contended == 0 and lk.wait_ns == 0


def test_timed_lock_contended_records_wait_and_histogram():
    old = metrics._install_registry(Registry())
    try:
        lk = TimedLock("unit_contended", threading.Lock())
        lk.acquire()
        t = threading.Thread(target=lambda: (lk.acquire(), lk.release()))
        t.start()
        time.sleep(0.05)
        lk.release()
        t.join(timeout=5)
        assert lk.contended == 1
        assert lk.wait_ns >= 30_000_000  # held ~50ms
        stats = hostobs.lock_stats()["unit_contended"]
        assert stats["contended"] == 1
        assert stats["max_wait_s"] >= 0.03
        snap = metrics.snapshot()
        assert (
            snap["counters"]["nomad.runtime.lock_contended.unit_contended"]
            == 1
        )
        s = snap["samples"]["nomad.runtime.lock_wait_seconds.unit_contended"]
        assert s["count"] == 1 and s["max"] >= 0.03
    finally:
        metrics._install_registry(old)


def test_timed_lock_condition_wait_notify():
    """threading.Condition over a TimedLock — both Lock and RLock
    inners — must wait/notify exactly like over the bare primitive
    (the broker and plan queue both build Conditions on theirs)."""
    for inner in (threading.Lock(), threading.RLock()):
        lk = TimedLock("unit_cv", inner)
        cv = threading.Condition(lk)
        got = []

        def waiter():
            with cv:
                got.append(cv.wait(5))

        t = threading.Thread(target=waiter)
        t.start()
        time.sleep(0.05)
        with cv:
            cv.notify_all()
        t.join(timeout=5)
        assert got == [True], type(inner)


def test_timed_lock_reentrant_rlock():
    lk = TimedLock("unit_rlock", threading.RLock())
    with lk:
        with lk:  # re-entrant acquire must not deadlock or count
            pass
    assert lk.contended == 0


# ---------------------------------------------------------------------------
# Sampler attribution units
# ---------------------------------------------------------------------------


def _spin_until(stop, ctx=None, span_name=""):
    """Busy loop, optionally under an open trace span."""
    if ctx is not None:
        with trace.use(ctx):
            with trace.span(ctx, span_name):
                while not stop.is_set():
                    sum(range(50))
    else:
        while not stop.is_set():
            sum(range(50))


def test_sampler_attributes_role_span_function():
    was = trace.enabled()
    trace.set_enabled(True)
    prof = HostProfiler(interval_s=0.002)
    stop = threading.Event()
    ctx = trace.start_trace("unit.trace")
    t = threading.Thread(
        target=_spin_until, args=(stop, ctx, "unit.span"),
        name="tpu-batch-solve", daemon=True,
    )
    try:
        prof.start()
        t.start()
        assert wait_until(
            lambda: any(
                k[0] == "solve" and k[1] == "unit.span"
                for k in list(prof._sites)
            ),
            10,
        ), prof.snapshot()["top_sites"]
    finally:
        stop.set()
        t.join(timeout=5)
        prof.stop()
        ctx.finish(record=False)
        trace.set_enabled(was)
    snap = prof.snapshot()
    site = next(
        s for s in snap["top_sites"]
        if s["role"] == "solve" and s["span"] == "unit.span"
    )
    assert "_spin_until" in site["site"] and site["seconds"] > 0
    assert snap["spans"]["unit.span"]["cpu_seconds"] > 0
    assert snap["threads"]["solve"]["busy_seconds"] > 0
    # collapsed stacks carry the role;span prefix and end in a count
    lines = prof.collapsed().splitlines()
    assert lines
    assert any(line.startswith("solve;unit.span;") for line in lines)
    for line in lines:
        stack, _, count = line.rpartition(" ")
        assert stack and int(count) >= 1


# What a thread does for the window, as code of this repo would: the
# leaf under each wait is a C call made from a plain function of a
# plain file — exactly what the deleted frame-name list called "busy".


def _burns(stop, held):
    while not stop.is_set():
        sum(range(50))


def _sleeps(stop, held):
    while not stop.is_set():
        time.sleep(0.01)


def _blocks_on_a_lock(stop, held):
    held.acquire()


def _parks(stop, held):
    stop.wait(20)


@pytest.mark.parametrize(
    "what, span, gets_cpu",
    [
        (_burns, "", True),
        (_sleeps, "unit.sleep", False),
        (_blocks_on_a_lock, "unit.lock", False),
        (_parks, "", False),
    ],
    ids=["burns_cpu", "asleep_in_a_span", "blocked_in_a_span", "parked"],
)
def test_cpu_goes_to_the_thread_that_ran_and_wait_to_the_span(
        what, span, gets_cpu):
    """The rule that replaced the frame-name heuristic: a thread is
    charged what its own CPU clock moved — so a thread asleep in
    time.sleep, blocked in Lock.acquire or parked in Event.wait gets
    none, whatever its leaf frame is called — and one that waits INSIDE
    a span is charged the wall it spent there, less its CPU, as wait."""
    was = trace.enabled()
    trace.set_enabled(True)
    prof = HostProfiler()
    stop, held = threading.Event(), threading.Lock()
    held.acquire()
    ctx = trace.start_trace("unit.trace") if span else None

    def body():
        if ctx is None:
            return what(stop, held)
        with trace.use(ctx), trace.span(ctx, span):
            what(stop, held)

    t = threading.Thread(target=body, name="unit-subject", daemon=True)
    try:
        prof._sample()  # takes the readings; charges nothing
        t.start()
        time.sleep(0.4)
        prof._sample()
    finally:
        stop.set()
        held.release()
        t.join(timeout=5)
        trace.set_enabled(was)
    snap = prof.snapshot()
    mine = snap["threads"].get("unit-subject", {})
    sites = [s for s in snap["top_sites"] if s["role"] == "unit-subject"]
    if gets_cpu:
        assert mine["busy_seconds"] > 0.1
        assert any(what.__name__ in s["site"] for s in sites), sites
    else:
        assert mine.get("busy_seconds", 0.0) < 0.05, mine
    if span:
        assert mine["wait_seconds"] > 0.3, mine
        assert snap["spans"][span]["wait_seconds"] > 0.3
        assert snap["spans"][span]["cpu_seconds"] < 0.05
    else:
        assert mine.get("wait_seconds", 0.0) == 0.0, mine


def test_a_reused_ident_does_not_inherit_a_role():
    """The ledger is keyed by the Thread object: a thread that gets the
    ident (and possibly the tid) of one that died takes its own role,
    and the dead one keeps what was last read of it."""
    prof = HostProfiler()
    prof._sample()

    def run_and_meet(name):
        stop = threading.Event()
        t = threading.Thread(target=_burns, args=(stop, None), name=name)
        t.start()
        role = hostobs._role_of(name)
        assert wait_until(
            lambda: prof._sample() and role in prof._role_stats, 10, 0.005
        )  # met alive, with CPU used
        stop.set()
        t.join(timeout=5)
        return t.ident

    rounds = reused = 0
    while rounds < 20 and not reused:
        rounds += 1
        ident = run_and_meet("raft-a")
        after_a = prof.snapshot()["threads"]["raft"]["busy_seconds"]
        reused = run_and_meet("tpu-batch-solve-b") == ident
    threads = prof.snapshot()["threads"]
    assert threads["solve"]["busy_seconds"] > 0
    # raft has what its own threads used: it kept it, and got no more
    assert threads["raft"]["busy_seconds"] == after_a > 0
    assert not any(th.name == "raft-a" for th in prof._threads)


def test_a_thread_shorter_than_a_pass_is_counted_through_the_exit_call():
    """Connection threads live under a pass: what they used arrives
    with note_thread_exit(), under the role their name gives."""
    old = hostobs._install(HostProfiler())
    prof = hostobs.profiler()
    try:
        prof._sample()

        def conn(note):
            t0 = time.thread_time()
            while time.thread_time() - t0 < 0.01:
                sum(range(50))
            if note:
                hostobs.note_thread_exit()

        for note, name in [(True, "rpc-conn"), (False, "unit-silent")] * 10:
            t = threading.Thread(target=conn, args=(note,), name=name)
            t.start()
            t.join(timeout=5)
        assert len(prof._exits) == 10
        snap = prof.snapshot()
    finally:
        hostobs._install(old)
    assert prof._exits == []
    assert 0.09 <= snap["threads"]["rpc"]["busy_seconds"] < 0.2
    # no pass met the silent ones: their CPU is the account's residual
    assert "unit-silent" not in snap["threads"]
    assert snap["threads"][hostobs.UNACCOUNTED]["busy_seconds"] >= 0.08


def test_the_account_closes_against_the_process_clock():
    """Sum of the roles' CPU + (unaccounted) is what the process's own
    CPU clock moved between two snapshots, to within the clocks' step,
    and no role's sum ever decreases."""
    prof = HostProfiler(interval_s=0.002)
    stop = threading.Event()
    prof.start()
    burners = [
        threading.Thread(target=_burns, args=(stop, None), name=n)
        for n in ("tpu-batch-solve", "plan-applier", "unit-other")
    ]
    try:
        s0 = prof.snapshot()["threads"]
        p0 = time.process_time_ns()
        for t in burners:
            t.start()
        time.sleep(0.2)
        mid = prof.snapshot()["threads"]
        time.sleep(0.2)
        stop.set()
        for t in burners:
            t.join(timeout=5)
        p1 = time.process_time_ns()
        s1 = prof.snapshot()["threads"]
    finally:
        stop.set()
        prof.stop()
    total = lambda s: sum(v["busy_seconds"] for v in s.values())  # noqa: E731
    assert total(s1) - total(s0) == pytest.approx((p1 - p0) / 1e9, abs=0.06)
    assert s1[hostobs.UNACCOUNTED]["busy_seconds"] >= 0.0
    for role, v in mid.items():
        if role != hostobs.UNACCOUNTED:  # the residual is no sum
            assert s1[role]["busy_seconds"] >= v["busy_seconds"], role
    assert s1["solve"]["busy_seconds"] > 0.05
    assert s1["host-profiler"]["busy_seconds"] > 0.0  # its own, counted


def test_a_failing_clock_read_is_skipped(monkeypatch):
    """A thread that died between the enumeration and the read gives
    OSError: the pass skips it and reads the others."""
    # a tid over the kernel's pid_max: what a thread that has gone
    # reads as, without this sandbox's quick reuse of tids
    dead_clock = hostobs._cpu_clock_of(1 << 27)
    with pytest.raises(OSError):
        time.clock_gettime_ns(dead_clock)
    stop = threading.Event()
    victim = threading.Thread(
        target=_burns, args=(stop, None), name="unit-victim", daemon=True
    )
    other = threading.Thread(
        target=_burns, args=(stop, None), name="unit-read", daemon=True
    )
    victim.start()
    other.start()
    real = hostobs._cpu_clock_of
    monkeypatch.setattr(
        hostobs, "_cpu_clock_of",
        lambda tid: dead_clock if tid == victim.native_id else real(tid),
    )
    prof = HostProfiler()
    try:
        prof._sample()
        time.sleep(0.1)
        prof._sample()  # must not raise
    finally:
        stop.set()
        victim.join(timeout=5)
        other.join(timeout=5)
    threads = prof.snapshot()["threads"]
    assert "unit-victim" not in threads
    assert threads["unit-read"]["busy_seconds"] > 0.01


def test_sampler_site_ledger_bounded():
    """Past max_sites, samples aggregate into (other) and the loss is
    counted — never silent growth, never silent drop."""
    prof = HostProfiler(interval_s=0.001, max_sites=16)
    stop = threading.Event()
    # 24 distinct leaf functions across threads > the 16-site bound
    fns = []
    ns: dict = {}
    for i in range(24):
        exec(
            f"def _unit_leaf_{i}(stop):\n"
            f"    while not stop.is_set(): sum(range(40))\n",
            ns,
        )
        fns.append(ns[f"_unit_leaf_{i}"])
    threads = [
        threading.Thread(target=fn, args=(stop,), daemon=True) for fn in fns
    ]
    for t in threads:
        t.start()
    try:
        prof.start()
        assert wait_until(lambda: prof.sites_evicted > 0, 15), (
            len(prof._sites)
        )
    finally:
        stop.set()
        prof.stop()
        for t in threads:
            t.join(timeout=5)
    # bounded: at most max_sites NAMED entries, plus the explicit
    # per-(role, span) (other) overflow buckets (overflow keeps its
    # role/span attribution; under the full suite foreign busy threads
    # contribute their own roles)
    others = [k for k in prof._sites if k[2] == hostobs.OTHER_SITE]
    assert others
    assert len(prof._sites) - len(others) <= prof.max_sites
    snap = prof.snapshot()
    assert snap["sites_evicted"] == prof.sites_evicted


_BACKOFF_SCRIPT = r"""
import sys, threading, time
sys.path.insert(0, %r)
from nomad_tpu.hostobs import HostProfiler

prof = HostProfiler(interval_s=0.001, idle_interval_s=0.05)
prof.start()
try:
    # Park in Event.wait: no thread's CPU clock moves. After 50
    # consecutive passes in which none moved the effective interval
    # climbs to the idle ceiling; assert on the published
    # cur_interval_s.
    parked = threading.Event()
    deadline = time.monotonic() + 15
    engaged = False
    while time.monotonic() < deadline and not engaged:
        parked.wait(0.3)
        engaged = prof.cur_interval_s >= prof.idle_interval_s
    assert engaged, prof.cur_interval_s
    assert prof.idle_samples > 0
    # ... and the first pass that sees a clock move snaps it back
    stop = threading.Event()
    def burn():
        while not stop.is_set():
            sum(range(50))
    t = threading.Thread(target=burn, daemon=True)
    t.start()
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline and engaged:
        time.sleep(0.05)
        engaged = prof.cur_interval_s >= prof.idle_interval_s
    stop.set()
    assert not engaged, prof.cur_interval_s
finally:
    prof.stop()
print("BACKOFF OK")
"""


def test_sampler_adaptive_idle_backoff():
    """An idle pass is one in which no other thread's CPU clock moved.
    Clean subprocess: inside the full suite, daemon threads leaked by
    earlier modules (raft tickers etc.) do run a little in every
    window, so the PROCESS never accumulates 50 consecutive idle passes
    and the backoff legitimately never engages. The property under test
    is the sampler's, not the suite's."""
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, "-c", _BACKOFF_SCRIPT % REPO_ROOT],
        capture_output=True,
        text=True,
        timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "BACKOFF OK" in proc.stdout


def test_a_slow_pass_stretches_the_period():
    """A pass may take a hundredth of the time: where it is slow (a
    clock read is a 6 us syscall on the chip's host, a thread each) the
    period stretches under load too, up to the idle ceiling, and the
    roles' CPU stays exact — the clocks are cumulative."""
    prof = HostProfiler(interval_s=0.001, idle_interval_s=0.08)
    real = prof._sample

    def slow_pass():
        time.sleep(0.004)  # 100 x 4 ms is over the ceiling
        real()
        return True  # "busy": the idle backoff is not what stretches it

    prof._sample = slow_pass
    prof.start()
    try:
        assert wait_until(lambda: prof.cur_interval_s >= 0.08, 10), (
            prof.cur_interval_s
        )
        prof._sample = real  # fast again: back toward the configured one
        assert wait_until(lambda: prof.cur_interval_s < 0.04, 10), (
            prof.cur_interval_s
        )
    finally:
        prof.stop()


def test_start_stop_refcounted_no_thread_leak():
    prof = HostProfiler(interval_s=0.01)
    prof.start()
    prof.start()  # second owner
    assert prof.running()
    prof.stop()
    assert prof.running(), "first stop must not kill the shared sampler"
    prof.stop()
    assert wait_until(lambda: not prof.running(), 5)
    assert _profiler_threads() == []


# ---------------------------------------------------------------------------
# GC + runtime telemetry
# ---------------------------------------------------------------------------


def test_gc_telemetry_and_paused_sections():
    from nomad_tpu import gctune

    old = metrics._install_registry(Registry())
    prof = HostProfiler(interval_s=0.01)
    prof.start()
    try:
        for _ in range(3):
            gc.collect()
        with gctune.paused_gc():
            with gctune.paused_gc():  # nested: ONE section
                pass
        snap = prof.snapshot()  # snapshot forces a flush
        assert sum(snap["gc"]["collections"].values()) >= 3
        assert snap["gc"]["pause_seconds_total"] > 0
        assert snap["gc"]["paused_sections"] == 1
        msnap = metrics.snapshot()
        assert msnap["counters"]["nomad.runtime.gc_collections"] >= 3
        assert msnap["counters"]["nomad.runtime.gc_collections.gen2"] >= 3
        assert msnap["counters"]["nomad.runtime.gc_paused_sections"] == 1
        assert (
            msnap["samples"]["nomad.runtime.gc_pause_seconds"]["count"] >= 3
        )
        # runtime gauges rode the same flush
        assert msnap["gauges"]["nomad.runtime.threads"] >= 1
        assert msnap["gauges"]["nomad.runtime.rss_bytes"] > 0
    finally:
        prof.stop()
        metrics._install_registry(old)
    # stopped: callback and hook are detached
    assert prof._gc_cb not in gc.callbacks
    assert gctune.on_section_end is None


def test_release_frozen_garbage_reclaims_frozen_cycles():
    """Cycles stranded in the permanent generation (a dropped frozen
    bench cluster) are invisible to gc.collect() but reclaimed by the
    unfreeze+collect+refreeze cycle."""
    import weakref

    from nomad_tpu import gctune

    class Node:
        pass

    a, b = Node(), Node()
    a.peer, b.peer = b, a
    ref = weakref.ref(a)
    gc.collect()
    gc.freeze()  # a/b now permanent, like a cluster frozen on exit
    del a, b
    gc.collect()  # refcount can't free the cycle; collect can't see it
    assert ref() is not None
    gctune.release_frozen_garbage()
    assert ref() is None


def test_gc_callback_buffer_bounded():
    prof = HostProfiler()
    prof._gc_pending.extend((0, 1000) for _ in range(1024))
    prof._gc_cb("start", {})
    prof._gc_cb("stop", {"generation": 0, "collected": 1})
    assert len(prof._gc_pending) == 1024  # bounded
    assert prof.gc_dropped == 1


# ---------------------------------------------------------------------------
# /v1/profile surface: routes, ACL, debug gating, bundle
# ---------------------------------------------------------------------------


def test_profile_routes_always_on_even_without_enable_debug(tmp_path):
    """enable_debug=False 404s pprof but never /v1/profile/* — the
    continuous profiler is observability, not a debug mode."""
    from nomad_tpu.agent import Agent, AgentConfig
    from nomad_tpu.api.client import APIError, NomadClient

    cfg = AgentConfig()
    cfg.server_enabled = True
    cfg.client_enabled = False
    cfg.dev_mode = False
    cfg.enable_debug = False
    cfg.data_dir = str(tmp_path)
    agent = Agent(cfg)
    agent.start()
    try:
        assert hostobs.running()
        api = NomadClient(f"http://127.0.0.1:{agent.http_addr[1]}")
        with pytest.raises(APIError) as e:
            api.get("/v1/agent/pprof/profile")
        assert e.value.status == 404
        snap = api.agent.profile_status()
        for key in (
            "samples", "busy_seconds", "top_sites", "spans", "threads",
            "gc", "locks", "runtime", "overhead",
        ):
            assert key in snap, key
        assert snap["running"] is True
        assert isinstance(api.agent.profile_collapsed(), str)
        # the debug bundle captures both profile surfaces
        from nomad_tpu.agent.debug import debug_bundle

        bundle = debug_bundle(api)
        assert "samples" in bundle["profile"], bundle["profile"]
        assert "collapsed" in bundle["profile_stacks"]
    finally:
        agent.shutdown()
    assert wait_until(lambda: _profiler_threads() == [], 5)


@pytest.fixture(scope="class")
def acl_agent(tmp_path_factory):
    # class-scoped (NOT module): later lifecycle tests assert the
    # process has zero sampler threads, which needs this agent torn
    # down the moment the ACL battery finishes
    from nomad_tpu.agent import Agent, AgentConfig

    cfg = AgentConfig.dev()
    cfg.acl_enabled = True
    cfg.data_dir = str(tmp_path_factory.mktemp("profile-acl"))
    a = Agent(cfg)
    a.start()
    assert wait_until(lambda: a.server.is_leader(), 15)
    yield a
    a.shutdown()


@pytest.fixture(scope="class")
def root(acl_agent):
    from nomad_tpu.api.client import NomadClient

    host, port = acl_agent.http_addr
    api = NomadClient(f"http://{host}:{port}")
    token = api.acl.bootstrap()
    return NomadClient(f"http://{host}:{port}", token=token.secret_id)


class TestProfileACL:
    """The bundle ACL battery extended to /v1/profile/*: anon 401,
    namespace-only token 403, agent:read 200 (same gate as /v1/metrics
    and /v1/solver/status)."""

    def _token(self, root, name, rules):
        root.acl.policy_apply(name, rules)
        return root.acl.token_create(name=name, policies=[name])

    @pytest.mark.parametrize(
        "path", ["/v1/profile/status", "/v1/profile/collapsed"]
    )
    def test_profile_routes_acl_battery(self, acl_agent, root, path):
        from nomad_tpu.api.client import APIError, NomadClient

        host, port = acl_agent.http_addr
        anon = NomadClient(f"http://{host}:{port}")
        with pytest.raises(APIError) as e:
            anon.get(path)
        assert e.value.status == 401
        tok = self._token(
            root, f"ns-only-{path.split('/')[-1]}",
            'namespace "default" { policy = "read" }',
        )
        nsr = NomadClient(f"http://{host}:{port}", token=tok.secret_id)
        with pytest.raises(APIError) as e:
            nsr.get(path)
        assert e.value.status == 403
        tok = self._token(
            root, f"agent-r-{path.split('/')[-1]}",
            'agent { policy = "read" }',
        )
        reader = NomadClient(f"http://{host}:{port}", token=tok.secret_id)
        assert reader.agent.profile_status()["samples"] >= 0
        # the raw capture stays agent:write (unchanged by this layer)
        with pytest.raises(APIError) as e:
            reader.get("/v1/agent/pprof/goroutine")
        assert e.value.status == 403


# ---------------------------------------------------------------------------
# Single-flight /v1/agent/pprof/profile
# ---------------------------------------------------------------------------


def test_pprof_capture_single_flight(tmp_path):
    from nomad_tpu.agent import Agent, AgentConfig
    from nomad_tpu.api.client import APIError, NomadClient

    cfg = AgentConfig()
    cfg.server_enabled = True
    cfg.client_enabled = False
    cfg.dev_mode = True
    cfg.data_dir = str(tmp_path)
    agent = Agent(cfg)
    agent.start()
    try:
        api = NomadClient(f"http://127.0.0.1:{agent.http_addr[1]}")
        results = {}

        def capture():
            results["first"] = api.get(
                "/v1/agent/pprof/profile", params={"seconds": "1.2"}
            )

        t = threading.Thread(target=capture, daemon=True)
        t.start()
        time.sleep(0.3)  # the first capture is mid-flight
        api2 = NomadClient(f"http://127.0.0.1:{agent.http_addr[1]}")
        with pytest.raises(APIError) as e:
            api2.get("/v1/agent/pprof/profile", params={"seconds": "1"})
        assert e.value.status == 429
        # Retry-After covers the in-flight capture's remaining time
        assert e.value.retry_after is not None
        assert 0 < e.value.retry_after <= 1.2
        t.join(timeout=15)
        assert "profile" in results["first"]
        # the guard released: a fresh capture succeeds
        out = api.get("/v1/agent/pprof/profile", params={"seconds": "0.2"})
        assert "profile" in out
    finally:
        agent.shutdown()


# ---------------------------------------------------------------------------
# Lifecycle: reload (SIGHUP), shared refcount, stop-during-inflight
# ---------------------------------------------------------------------------


def test_profiler_lifecycle_across_reload_and_shared_agents(tmp_path):
    import copy

    from nomad_tpu.agent import Agent, AgentConfig

    cfg = AgentConfig()
    cfg.server_enabled = True
    cfg.client_enabled = False
    cfg.dev_mode = True
    cfg.data_dir = str(tmp_path / "a1")
    a1 = Agent(cfg)
    a1.start()
    try:
        assert len(_profiler_threads()) == 1
        cfg2 = AgentConfig()
        cfg2.server_enabled = True
        cfg2.client_enabled = False
        cfg2.dev_mode = True
        cfg2.data_dir = str(tmp_path / "a2")
        a2 = Agent(cfg2)
        a2.start()
        try:
            # process-global singleton: two agents, ONE sampler thread
            assert len(_profiler_threads()) == 1
            # reload a1 with host_profile off: a2 still owns a ref
            off = copy.deepcopy(a1.config)
            off.host_profile_enabled = False
            assert "host_profile" in a1.reload(off)
            assert hostobs.running(), "a2's refcount must keep it alive"
            # back on (and a new interval): reported + applied
            on = copy.deepcopy(a1.config)
            on.host_profile_enabled = True
            on.host_profile_interval_ms = 25.0
            assert "host_profile" in a1.reload(on)
            assert hostobs.profiler().interval_s == pytest.approx(0.025)
            assert len(_profiler_threads()) == 1
        finally:
            a2.shutdown()
        assert hostobs.running(), "a1 still holds a ref"
    finally:
        a1.shutdown()
    assert wait_until(lambda: _profiler_threads() == [], 5), (
        "sampler thread leaked past the last owner's shutdown"
    )


def test_shutdown_during_inflight_pprof_capture(tmp_path):
    """Agent stop while a wall-clock capture occupies a handler thread:
    shutdown must return promptly and the sampler thread must not leak
    (the capture thread is a daemon; its socket dies with the
    server)."""
    from nomad_tpu.agent import Agent, AgentConfig
    from nomad_tpu.api.client import NomadClient

    cfg = AgentConfig()
    cfg.server_enabled = True
    cfg.client_enabled = False
    cfg.dev_mode = True
    cfg.data_dir = str(tmp_path)
    agent = Agent(cfg)
    agent.start()
    api = NomadClient(f"http://127.0.0.1:{agent.http_addr[1]}")

    def capture():
        try:
            api.get("/v1/agent/pprof/profile", params={"seconds": "3"})
        except Exception:
            pass  # the shutdown may sever the connection — expected

    t = threading.Thread(target=capture, daemon=True)
    t.start()
    time.sleep(0.3)
    t0 = time.monotonic()
    agent.shutdown()
    assert time.monotonic() - t0 < 10, "shutdown blocked on the capture"
    assert wait_until(lambda: _profiler_threads() == [], 5)
    t.join(timeout=10)


# ---------------------------------------------------------------------------
# E2E acceptance: the real TPUBatchWorker, span-correlated attribution
# ---------------------------------------------------------------------------


def _c2m_jobs(prefix: str, n_jobs: int = 12):
    from nomad_tpu.structs import Constraint, Spread

    jobs = []
    for j in range(n_jobs):
        job = mock.job(id=f"{prefix}-{j}")
        job.datacenters = ["dc1", "dc2"]
        tg = job.task_groups[0]
        tg.count = 10
        tg.tasks[0].resources.cpu = 100
        tg.tasks[0].resources.memory_mb = 64
        tg.tasks[0].resources.networks = []
        job.constraints.append(
            Constraint("${attr.kernel.name}", "linux", "=")
        )
        job.spreads = [Spread(attribute="${node.datacenter}", weight=50)]
        jobs.append(job)
    return jobs


def test_e2e_host_attribution_acceptance(tmp_path, capsys):
    """The e2e acceptance batch: c2m-style waves through the real
    pipelined TPUBatchWorker with tracing on — the solve and commit
    threads profile as DISTINCT roles, samples carry worker span names,
    nomad.host.* / nomad.runtime.* ride /v1/metrics, and the same
    snapshot renders via `operator profile status` and the Host row in
    `operator top`."""
    from nomad_tpu.agent import Agent, AgentConfig
    from nomad_tpu.api.client import NomadClient
    from nomad_tpu.cli.main import (
        cmd_operator_profile_status,
        cmd_operator_top,
    )
    from nomad_tpu.structs.node_class import compute_node_class

    old_reg = metrics._install_registry(Registry())
    old_prof = hostobs._install(HostProfiler(interval_s=0.002))
    was_traced = trace.enabled()
    cfg = AgentConfig(
        server_enabled=True,
        dev_mode=True,
        use_tpu_batch_worker=True,
        trace_enabled=True,
        host_profile_interval_ms=2.0,
        data_dir=str(tmp_path / "agent"),
    )
    agent = Agent(cfg)
    try:
        agent.start()
        srv = agent.server.server
        for i in range(16):
            n = mock.node()
            n.datacenter = ["dc1", "dc2"][i % 2]
            n.resources.cpu = 4000
            n.resources.memory_mb = 8192
            n.computed_class = compute_node_class(n)
            srv.node_register(n)

        def drive_wave(prefix):
            jobs = _c2m_jobs(prefix)
            for job in jobs:
                srv.raft_apply("job_register", (job, None))
            evals = [mock.eval_for_job(job) for job in jobs]
            srv.eval_broker.enqueue_all(evals)
            assert wait_until(
                lambda: all(
                    len(srv.state.allocs_by_job("default", j.id)) >= 10
                    for j in jobs
                ),
                60,
            ), f"wave {prefix} never placed"

        api = NomadClient(f"http://127.0.0.1:{agent.http_addr[1]}")
        for wave in range(4):  # enough solve wall for 2ms sampling
            drive_wave(f"wave{wave}")
        snap = api.agent.profile_status(top=200)
        assert snap["running"] and snap["samples"] > 0
        assert snap["busy_seconds"] > 0
        # the pipelined worker's stages are distinct roles
        assert "solve" in snap["threads"], snap["threads"].keys()
        # span correlation: samples carry the worker's span names (the
        # batch root or any stage span — scheduling-dependent)
        spanned = {s["span"] for s in snap["top_sites"]} - {"-"}
        assert spanned, snap["top_sites"][:5]
        worker_spans = {
            "tpu.batch", "solve.dispatch", "broker.drain", "commit.finish",
            "commit.handoff", "plan.submit", "snapshot.wait", "eval.ack",
            "eval",
        }
        assert spanned & worker_spans, spanned
        # collapsed stacks exist and parse
        text = api.agent.profile_collapsed()
        assert text and all(
            line.rpartition(" ")[2].isdigit()
            for line in text.splitlines()
        )
        # nomad.host.* provider gauges + nomad.runtime.* on /v1/metrics
        msnap = api.agent.metrics()
        assert msnap["gauges"]["nomad.host.samples"] > 0
        assert msnap["gauges"]["nomad.host.busy_seconds"] > 0
        assert msnap["gauges"]["nomad.runtime.threads"] > 1
        prom = api.agent.metrics_prometheus()
        assert "nomad_host_samples" in prom
        assert "nomad_runtime_rss_bytes" in prom

        # `operator profile status` renders the same snapshot
        args = SimpleNamespace(
            address=f"http://127.0.0.1:{agent.http_addr[1]}",
            token=None, region=None, as_json=False,
        )
        capsys.readouterr()
        assert cmd_operator_profile_status(args) == 0
        out = capsys.readouterr().out
        assert "Top CPU sites" in out and "CPU by role" in out
        assert "GC" in out and "Runtime" in out
        # ... and `operator top` gained the Host row
        targs = SimpleNamespace(
            address=f"http://127.0.0.1:{agent.http_addr[1]}",
            token=None, region=None, interval=2.0, n=0, once=True,
        )
        assert cmd_operator_top(targs) == 0
        out = capsys.readouterr().out
        assert "Host" in out and "busy" in out
    finally:
        agent.shutdown()
        trace.set_enabled(was_traced)
        metrics._install_registry(old_reg)
        hostobs._install(old_prof)
    assert wait_until(lambda: _profiler_threads() == [], 5)


# ---------------------------------------------------------------------------
# Overhead gate: profiled vs unprofiled throughput (clean subprocess)
# ---------------------------------------------------------------------------


OVERHEAD_SCRIPT = r"""
import json, random, time

from nomad_tpu import hostobs, mock
from nomad_tpu.scheduler.tpu import solve_eval_batch
from nomad_tpu.testing import build_cluster

# The acceptance criterion's two workloads: 10 nodes, one job of 10
# (host fast path) and a c2m-SHAPED constrained/spread batch (scaled so
# a clean-subprocess best-of converges inside CI time; the shape — not
# the node count — decides which code runs). "Profiled" means the
# sampler thread is RUNNING and recording at the production 10ms
# cadence; "unprofiled" parks the same thread on the recording gate, so
# the measured delta is exactly what production pays for leaving the
# profiler on.
hostobs.configure(interval_s=0.010)
hostobs.start()

def once(profiled: bool, snap, h, evals, reps: int) -> float:
    hostobs.reset_stats()
    hostobs.set_enabled(profiled)
    try:
        t0 = time.perf_counter()
        for _ in range(reps):
            solve_eval_batch(snap, h, evals)
        return time.perf_counter() - t0
    finally:
        hostobs.set_enabled(True)


def measure(n_nodes, n_jobs, count, constrained, reps):
    import gc
    gc.collect()
    h, jobs = build_cluster(n_nodes, n_jobs, count, constrained)
    snap = h.snapshot()
    evals = [mock.eval_for_job(j) for j in jobs]
    solve_eval_batch(snap, h, evals)  # warm before either measured side
    # randomized interleave, MINIMUM per side (the established
    # overhead-gate recipe): load spikes can only RAISE a side's
    # samples, never lower its min.
    order = [False, True] * 24
    random.shuffle(order)
    best = {False: float("inf"), True: float("inf")}
    for on in order:
        best[on] = min(best[on], once(on, snap, h, evals, reps))
    return {
        "ratio": best[False] / best[True],
        "off_ms": best[False] * 1e3,
        "on_ms": best[True] * 1e3,
    }


out = {
    "smoke": measure(10, 1, 10, False, reps=10),
    "c2m_shaped": measure(200, 4, 50, True, reps=2),
}
print(json.dumps(out))
"""


def test_profiled_throughput_vs_unprofiled_gate():
    """Acceptance gate: smoke and c2m-shaped scheduling throughput with
    the host profiler ON stays >= 0.95x the unprofiled path — clean
    subprocess, randomized-interleave minima (the round-10
    methodology: the suite's daemon threads make in-process timing
    comparisons noise)."""
    import subprocess
    import sys

    # Box-load noise is one-sided (the measured overhead is ~1%): each
    # workload passes on its BEST attempt independently.
    best: dict = {}
    attempts = []
    for _ in range(3):
        proc = subprocess.run(
            [sys.executable, "-c", OVERHEAD_SCRIPT],
            capture_output=True,
            text=True,
            timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            cwd=REPO_ROOT,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        out = json.loads(proc.stdout.strip().splitlines()[-1])
        attempts.append({k: round(v["ratio"], 3) for k, v in out.items()})
        for k, v in out.items():
            best[k] = max(best.get(k, 0.0), v["ratio"])
        if all(v >= 0.95 for v in best.values()):
            return
    pytest.fail(
        f"profiled throughput < 0.95x unprofiled across all attempts "
        f"(best per workload {best}): {attempts}"
    )


def test_private_profiler_restores_gctune_hook():
    """A PRIVATE HostProfiler (run_soak's measurement apparatus) must
    hand gctune.on_section_end back to its previous owner on stop —
    nulling it would permanently blind a co-resident global profiler's
    paused-section accounting."""
    from nomad_tpu import gctune, hostobs

    before = gctune.on_section_end
    outer = hostobs.HostProfiler(interval_s=0.05)
    outer.start()
    try:
        assert gctune.on_section_end == outer.note_gc_section
        inner = hostobs.HostProfiler(interval_s=0.05)
        inner.start()
        try:
            assert gctune.on_section_end == inner.note_gc_section
        finally:
            inner.stop()
        # the inner (soak-private) instance restored the outer owner
        assert gctune.on_section_end == outer.note_gc_section
    finally:
        outer.stop()
    assert gctune.on_section_end == before
