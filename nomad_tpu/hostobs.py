"""Continuous host-profiling: span-correlated CPU attribution, runtime
telemetry (GC / RSS / fds / threads), and lock-wait accounting.

The solver's own telemetry (VERDICT r5, trace/solverobs) shows ~86% of a
c2m batch is host-side Python — but nothing attributed that second to
CODE: traces give stage wall time, the compile ledger covers the device,
and the only CPU profiler was the on-demand, enable_debug-gated capture
in agent/debug.py. This module is the always-on layer, in the spirit of
fleet continuous profilers (Google-Wide Profiling; Pyroscope/Parca):

  * thread-CPU ledger + sampler — the kernel keeps a cumulative CPU
    clock for every thread; a background thread reads every live Python
    thread's on an interval adaptive to load and charges what each USED
    since the previous pass (CPU, never wall: under one interpreter
    lock a span's wall is mostly the wait for the other threads) to its
    role — exact however rarely the sampler ran — and, walking only
    the threads that ran, to **(thread role x active trace span x leaf
    function)** (``trace.thread_spans()``). A thread inside a span is
    also charged the pass's wall less its CPU as **wait**. Threads
    shorter than a pass hand their clock in as they end
    (``note_thread_exit``); the account closes against the process's
    CPU clock in the role ``(unaccounted)``. Ledgers are bounded
    (site/stack overflow aggregates into an explicit ``(other)`` bucket
    — coverage loss is COUNTED, never silent), and a thread whose clock
    stood still costs one clock read: no frame, tuple or string.
  * runtime telemetry — GC pause/collection accounting via
    ``gc.callbacks`` (pauses are buffered in the callback and flushed to
    the metrics registry by the sampler thread: the callback itself can
    fire while ANY lock — including the registry's — is held by the
    collecting thread, so it must never take one), gctune paused-GC
    section accounting (gctune.on_section_end), and RSS / fd-count /
    thread-count / gc-generation gauges sampled once per flush interval.
  * lock-wait attribution — :class:`TimedLock` wraps the hot locks
    (eval broker, plan queue, metrics registry): the uncontended path is
    a single non-blocking try-acquire (no timestamps, no allocation);
    only a CONTENDED acquire takes two clock reads and lands in the
    per-lock wait ledger + ``nomad.runtime.lock_wait_seconds.<lock>``.

Deliberately a stdlib-only leaf (like solverobs/faultplane): metrics and
trace are imported lazily inside functions so metrics.py itself can use
TimedLock without an import cycle.

Surfaces: ``GET /v1/profile/status`` (summary) and
``GET /v1/profile/collapsed`` (collapsed-stack flamegraph text) behind
``agent:read`` — always on, unlike the enable_debug-gated pprof capture;
``operator profile status|top|stacks``; a Host row in ``operator top``;
the ``operator debug`` bundle; and the bench's per-config
``host_attribution`` block. All ``nomad.host.*`` / ``nomad.runtime.*``
names are catalogued in docs/metrics.md (source-walk enforced). Design
notes and flamegraph reading: docs/profiling.md.
"""

from __future__ import annotations

import gc
import os
import sys
import threading
import time
import weakref
from typing import Optional

now_ns = time.monotonic_ns

# -- bounds --------------------------------------------------------------
# Sites are (role, span, function) triples — a closed set in practice
# (the codebase has a few hundred hot functions); the bound only matters
# under pathological frame churn (generated code), where overflow lands
# in "(other)" and sites_evicted counts the loss.
MAX_SITES = 2048
MAX_STACKS = 8192
MAX_DEPTH = 48
OTHER_SITE = "(other)"
UNACCOUNTED = "(unaccounted)"
WALK_NS = 1_000_000  # CPU a thread adds up between two walks of its stack

_enabled = True


def enabled() -> bool:
    return _enabled


def set_enabled(on: bool) -> None:
    """Recording gate (GIL-atomic flag): the sampler thread keeps
    running but reads no clock and walks no frame when off. The bench uses
    this to exclude cluster-build time from attribution windows and as
    the unprofiled side of the overhead gate; production leaves it on."""
    global _enabled
    _enabled = bool(on)


# -- lock-wait attribution ----------------------------------------------

_lock_registry: "weakref.WeakSet[TimedLock]" = weakref.WeakSet()


class TimedLock:
    """A Lock/RLock wrapper attributing contended-acquire wait time.

    Fast path: one non-blocking try-acquire — an uncontended lock costs
    a single extra C call, no clock reads, no allocation. Contended
    path: two monotonic_ns reads around the blocking acquire, instance
    counters (safe unsynchronized: the incrementing thread HOLDS the
    lock), and a ``nomad.runtime.lock_wait_seconds.<name>`` histogram
    observation unless ``histogram=False`` — the metrics registry's own
    lock MUST pass False (observing would re-acquire the very lock the
    caller now holds: self-deadlock).

    Condition-compatible: ``_release_save``/``_acquire_restore``/
    ``_is_owned`` delegate to the inner primitive where it provides them
    (RLock) and fall back to the stdlib default shapes otherwise, so
    ``threading.Condition(TimedLock(...))`` behaves exactly like
    Condition over the bare primitive. Pass the inner lock explicitly
    (``TimedLock("broker", threading.RLock())``) so the racecheck
    lock-order detector classes it by the REAL allocation site.
    """

    __slots__ = (
        "name", "_inner", "_histogram",
        "contended", "wait_ns", "max_wait_ns", "__weakref__",
    )

    def __init__(self, name: str, inner=None, histogram: bool = True) -> None:
        self.name = name
        self._inner = inner if inner is not None else threading.Lock()
        self._histogram = histogram
        self.contended = 0
        self.wait_ns = 0
        self.max_wait_ns = 0
        _lock_registry.add(self)

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        inner = self._inner
        if inner.acquire(False):
            return True
        if not blocking:
            return False
        t0 = now_ns()
        ok = inner.acquire(True, timeout)
        dt = now_ns() - t0
        if ok:
            # serialized by the lock itself: plain int ops are safe
            self.contended += 1
            self.wait_ns += dt
            if dt > self.max_wait_ns:
                self.max_wait_ns = dt
            if self._histogram and _enabled:
                from . import metrics

                metrics.incr(f"nomad.runtime.lock_contended.{self.name}")
                metrics.observe(
                    f"nomad.runtime.lock_wait_seconds.{self.name}", dt / 1e9
                )
        return ok

    def release(self) -> None:
        self._inner.release()

    def __enter__(self) -> "TimedLock":
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()

    def locked(self) -> bool:
        return self._inner.locked()

    # Condition plumbing (threading.Condition grabs these at __init__;
    # wait()'s release/reacquire cycles bypass the timing on purpose —
    # a Condition sleeper is parked, not contending).

    def _release_save(self):
        f = getattr(self._inner, "_release_save", None)
        if f is not None:
            return f()
        self._inner.release()

    def _acquire_restore(self, state) -> None:
        f = getattr(self._inner, "_acquire_restore", None)
        if f is not None:
            f(state)
            return
        self._inner.acquire()

    def _is_owned(self) -> bool:
        f = getattr(self._inner, "_is_owned", None)
        if f is not None:
            return f()
        if self._inner.acquire(False):
            self._inner.release()
            return False
        return True

    def stats(self) -> dict:
        return {
            "contended": self.contended,
            "wait_seconds_total": round(self.wait_ns / 1e9, 6),
            "max_wait_s": round(self.max_wait_ns / 1e9, 6),
        }


def lock_stats() -> dict[str, dict]:
    """Aggregate TimedLock stats by lock name across live instances
    (in-process test clusters run several brokers; operators run one)."""
    agg: dict[str, dict] = {}
    for lk in list(_lock_registry):
        cur = agg.setdefault(
            lk.name,
            {"contended": 0, "wait_seconds_total": 0.0, "max_wait_s": 0.0},
        )
        s = lk.stats()
        cur["contended"] += s["contended"]
        cur["wait_seconds_total"] = round(
            cur["wait_seconds_total"] + s["wait_seconds_total"], 6
        )
        cur["max_wait_s"] = max(cur["max_wait_s"], s["max_wait_s"])
    return agg


# -- thread-role classification ------------------------------------------

_ROLE_PREFIXES = (
    ("MainThread", "main"),
    ("tpu-batch-solve", "solve"),
    ("tpu-batch-commit", "commit"),
    ("worker", "worker"),
    ("plan-applier", "applier"),
    ("http-agent", "http"),
    ("rpc-", "rpc"),
    ("raft", "raft"),
    ("serf", "serf"),
    ("broker-delayed", "broker"),
    ("statsd-sink", "telemetry"),
    ("heartbeat", "heartbeat"),
)


def _role_of(name: str) -> str:
    for prefix, role in _ROLE_PREFIXES:
        if name.startswith(prefix):
            return role
    if "process_request_thread" in name:  # ThreadingHTTPServer workers
        return "http"
    if name.startswith("Thread-"):
        return "other"
    # bounded by the live thread-name set; strip trailing numbering so
    # "logmon-3" and "logmon-7" share a role
    return name.rstrip("0123456789-") or "other"


def _cpu_clock_of(native_id: int) -> int:
    """The clock id of one thread's cumulative CPU clock, for
    ``time.clock_gettime_ns`` from any thread. Built from the kernel's
    tid (CPUCLOCK_SCHED | per-thread: the very number
    ``time.pthread_getcpuclockid(ident)`` returns) and NOT through that
    call: it dereferences the pthread_t, a stale pointer once the thread
    is gone, where a dead tid can only make the read raise OSError (or,
    reused, read another thread of THIS process: the kernel admits no
    other — and a pass reads only threads it has just found alive)."""
    return ((~native_id) << 3) | 6


# -- the profiler --------------------------------------------------------


class HostProfiler:
    """One process-wide instance (module functions delegate); tests and
    the bench may install a fresh one via :func:`_install`.

    Writer discipline: ledgers are written by a pass alone, under
    ``_lock`` (the sampler's, and the one snapshot() takes first; GC
    callbacks and exiting threads buffer into bounded lists a pass
    drains); readers (snapshot/collapsed, any thread) copy under
    ``_lock``, which is uncontended at steady state — held for the
    microseconds of one pass."""

    def __init__(
        self,
        interval_s: float = 0.010,
        idle_interval_s: float = 0.10,
        flush_interval_s: float = 10.0,
        max_sites: int = MAX_SITES,
        max_stacks: int = MAX_STACKS,
        max_depth: int = MAX_DEPTH,
    ) -> None:
        self.interval_s = max(0.001, float(interval_s))
        self.idle_interval_s = max(self.interval_s, float(idle_interval_s))
        self.flush_interval_s = max(0.05, float(flush_interval_s))
        # the sampler's EFFECTIVE period right now (backoff observable)
        self.cur_interval_s = self.interval_s
        self.max_sites = max(16, int(max_sites))
        self.max_stacks = max(16, int(max_stacks))
        self.max_depth = max(4, int(max_depth))
        self._lock = threading.Lock()
        # Serializes _flush: the sampler's periodic flush and a
        # snapshot() reader (HTTP thread) must not drain the GC-pending
        # buffers concurrently — the copy+clear is two bytecodes, and a
        # double drain double-counts every pause. Ordered BEFORE _lock
        # and the metrics registry lock everywhere.
        self._flush_lock = threading.Lock()
        # (role, span, site) -> [samples, busy_ns]
        self._sites: dict[tuple, list] = {}
        # collapsed "role;span;f0;f1;...;leaf" -> samples
        self._stacks: dict[str, int] = {}
        # source -> busy ns: the clusterobs thread->source registry's
        # dimension ("handler CPU x source node") — bounded, overflow
        # folds into "(other)" like the site ledger
        self._source_ns: dict[str, int] = {}
        self.max_sources = 512
        # role, span -> [samples, cpu ns, wait ns]. Wait is wall less
        # CPU inside a span, summed SIGNED and shown never below 0: a
        # clock that steps (10 ms on the chip's host) shows no step in
        # one pass and two in the next
        self._role_stats: dict[str, list] = {}
        self._span_stats: dict[str, list] = {}
        # the thread-CPU ledger, thread -> [role, clock id, last ns,
        # CPU not yet walked], keyed by the Thread OBJECT (held, so
        # neither a reused ident nor a reused tid can inherit a role);
        # what note_thread_exit handed in, and who did (never read
        # again while they linger)
        self._threads: dict = {}
        self._exits: list = []  # (thread, thread_time_ns)
        self._gone: set = set()
        self._proc_ns = 0  # the process's CPU over the same passes
        self._proc_last = 0
        self._last_ns = now_ns()
        # False: the next pass only takes readings (start, or recording
        # was off): CPU used before it belongs to no window
        self._primed = False
        self.samples = 0
        self.idle_samples = 0
        self.busy_ns = 0
        self.sites_evicted = 0
        self.stacks_dropped = 0
        self._sampler_ns = 0  # time spent inside sample passes
        self._started_ns = 0
        # code object -> (qualified frame label, leaf-site label)
        self._code_cache: dict = {}
        # GC accounting (callback-side buffers; sampler flushes)
        self._gc_t0 = 0
        self._gc_pending: list[tuple[int, int]] = []  # (gen, pause_ns)
        self.gc_dropped = 0
        self.gc_collections = [0, 0, 0]
        self.gc_collected = 0
        self.gc_pause_ns = 0
        self.gc_pause_max_ns = 0
        # gctune paused-GC sections (hook-side buffer; sampler flushes)
        self._section_pending: list[int] = []
        self.gc_sections = 0
        self.gc_section_ns = 0
        self._gc_collected_flushed = 0
        # lifecycle
        self._refs = 0
        self._ref_lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._provider_handle = None
        self._prev_section_hook = None

    # -- lifecycle -------------------------------------------------------

    def start(self) -> None:
        """Refcounted: every Agent (and the bench) calls start/stop in
        pairs; one sampler thread serves the whole process."""
        with self._ref_lock:
            self._refs += 1
            if self._thread is not None and self._thread.is_alive():
                return
            self._stop = threading.Event()
            self._started_ns = now_ns()
            self._primed = False
            self._thread = threading.Thread(
                target=self._run, args=(self._stop,), daemon=True,
                name="host-profiler",
            )
            gc.callbacks.append(self._gc_cb)
            from . import gctune

            # save the previous owner: a PRIVATE instance (run_soak's
            # measurement apparatus) must hand the hook back to a
            # co-resident global profiler on stop, not null it out
            self._prev_section_hook = gctune.on_section_end
            gctune.on_section_end = self.note_gc_section
            if self._provider_handle is None:
                from . import metrics

                self._provider_handle = metrics.register_provider(
                    "nomad.host", self._provider
                )
            self._thread.start()

    def stop(self) -> None:
        with self._ref_lock:
            if self._refs > 0:
                self._refs -= 1
            if self._refs > 0 or self._thread is None:
                return
            self._stop.set()
            t = self._thread
            self._thread = None
        t.join(timeout=2)
        try:
            gc.callbacks.remove(self._gc_cb)
        except ValueError:
            pass
        from . import gctune

        if gctune.on_section_end == self.note_gc_section:
            gctune.on_section_end = self._prev_section_hook
        self._prev_section_hook = None

    def running(self) -> bool:
        t = self._thread
        return t is not None and t.is_alive()

    def configure(
        self,
        interval_s: Optional[float] = None,
        flush_interval_s: Optional[float] = None,
        idle_interval_s: Optional[float] = None,
    ) -> None:
        """Operator knobs (telemetry { host_profile_interval }, SIGHUP
        reload): picked up by the sampler on its next wakeup.
        idle_interval_s clamps the idle backoff ceiling — the bench's
        attribution passes pin it to the busy interval so short bursts
        after long idle builds aren't sampled at the backed-off rate."""
        if interval_s is not None:
            self.interval_s = max(0.001, float(interval_s))
            self.idle_interval_s = max(self.interval_s, self.idle_interval_s)
        if idle_interval_s is not None:
            self.idle_interval_s = max(
                self.interval_s, float(idle_interval_s)
            )
        if flush_interval_s is not None:
            self.flush_interval_s = max(0.05, float(flush_interval_s))

    def reset_stats(self) -> None:
        """Forget attribution (bench per-config isolation; the sampler
        thread and lifecycle state are untouched)."""
        self._sample()  # CPU used up to here belongs to what is forgotten
        with self._flush_lock, self._lock:
            self._sites.clear()
            self._stacks.clear()
            self._span_stats.clear()
            self._source_ns.clear()
            self._role_stats.clear()
            self._proc_ns = 0
            self.samples = 0
            self.idle_samples = 0
            self.busy_ns = 0
            self.sites_evicted = 0
            self.stacks_dropped = 0
            self._sampler_ns = 0
            self._started_ns = now_ns()
            self.gc_collections = [0, 0, 0]
            self.gc_collected = 0
            self.gc_pause_ns = 0
            self.gc_pause_max_ns = 0
            self.gc_sections = 0
            self.gc_section_ns = 0
            self._gc_collected_flushed = 0
            del self._gc_pending[:]
            del self._section_pending[:]
        for lk in list(_lock_registry):
            lk.contended = 0
            lk.wait_ns = 0
            lk.max_wait_ns = 0

    # -- GC hooks (MUST NOT touch the metrics registry: the collector
    # can fire while the collecting thread holds any lock, including
    # the registry's — the sampler flushes these buffers instead) ------

    def _gc_cb(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = now_ns()
            return
        t0 = self._gc_t0
        if not t0:
            return
        self._gc_t0 = 0
        if not _enabled:
            return
        dt = now_ns() - t0
        gen = int(info.get("generation", 0))
        # GIL-atomic appends; bounded so a collection storm between
        # flushes can't grow the buffer without bound
        if len(self._gc_pending) < 1024:
            self._gc_pending.append((gen, dt))
        else:
            self.gc_dropped += 1
        self.gc_collected += int(info.get("collected", 0))
        # onto the collecting thread's trace, if it runs under one: a
        # pause inside solve.dispatch is `gc.pause`, not self time
        # (lock-free like the append above; one flag test when off)
        from . import trace

        trace.stage_attrs("gc.pause", dt, generation=gen)

    def note_gc_section(self, dur_ns: int) -> None:
        """gctune.paused_gc outermost-exit hook: how long the collector
        was deliberately off for a batch (docs/profiling.md — a long
        paused section means the RE-ENABLE pays one big young-gen
        scan)."""
        if not _enabled:
            return
        if len(self._section_pending) < 1024:
            self._section_pending.append(int(dur_ns))

    def note_thread_exit(self) -> None:
        """The last act of a thread that may live under a pass (an HTTP
        or RPC connection): hand its own CPU clock to the ledger, which
        charges its role with what no pass had read. One append, no
        lock; bounded like the GC buffers (the loss shows as
        ``(unaccounted)``)."""
        if _enabled and len(self._exits) < 4096:
            self._exits.append(
                (threading.current_thread(), time.thread_time_ns())
            )

    # -- sampler ---------------------------------------------------------

    def _run(self, stop: threading.Event) -> None:
        interval = self.interval_s
        idle_streak = 0
        next_flush = 0.0
        while not stop.wait(interval):
            self.cur_interval_s = interval
            t0 = now_ns()
            if not _enabled:
                self._primed = False
            elif self._sample():
                idle_streak = 0
                interval = self.interval_s
            else:
                # adaptive idle backoff: a quiet agent converges to
                # idle_interval_s, ~10x fewer wakeups
                idle_streak += 1
                if idle_streak >= 50:
                    interval = min(interval * 2, self.idle_interval_s)
            now = time.monotonic()
            if now >= next_flush:
                next_flush = now + self.flush_interval_s
                try:
                    self._flush()
                except Exception:  # flush must never kill the sampler
                    pass
            self._sampler_ns += (took := now_ns() - t0)
            # a pass holds the interpreter for a syscall a thread (6 us
            # each on the chip's host): at most a hundredth of the time
            interval = max(interval, min(took * 1e-7, self.idle_interval_s))

    def _sample(self) -> bool:
        """One pass over every live thread's CPU clock (the sampler's,
        or snapshot()'s on the reader's thread). What a thread used
        since the previous pass goes to its role — exact, however long
        ago that was — and, at the frame it is met in, to (role, span,
        site) and the collapsed stack; inside a span, the pass's wall
        less that CPU is wait. Returns whether any other thread's clock
        moved (drives the adaptive interval)."""
        from . import clusterobs as _clusterobs, trace as _trace

        me = threading.get_ident()
        spans = _trace.thread_spans()
        sources = _clusterobs.thread_sources()
        read = time.clock_gettime_ns
        code_cache = self._code_cache
        ledger = self._threads
        busy_any, frames = False, None
        with self._lock:
            t = now_ns()
            # the cap is for wait alone (a sampler starved for seconds
            # must not call the whole gap a wait); CPU needs none
            dt_ns = min(t - self._last_ns, 2_000_000_000)
            self._last_ns = t
            credit, self._primed = self._primed, True
            proc = time.process_time_ns()
            if credit:
                self.samples += 1
                self._proc_ns += proc - self._proc_last
            self._proc_last = proc
            n = len(self._exits)  # the exiting threads only append
            for th, cpu in self._exits[:n]:
                ent = ledger.pop(th, None)
                self._gone.add(th)
                cpu -= ent[2] if ent else 0
                if credit and cpu > 0:
                    self.busy_ns += cpu
                    self._charge(self._role_stats, _role_of(th.name), cpu)
            del self._exits[:n]
            threads = threading.enumerate()
            for th in threads:
                ent = ledger.get(th)
                if ent is None:
                    if th.native_id is None or th in self._gone:
                        continue
                    # born since the last pass: all it used counts
                    ent = ledger[th] = [
                        _role_of(th.name), _cpu_clock_of(th.native_id), 0, 0
                    ]
                try:
                    cpu = read(ent[1])
                except OSError:
                    continue  # died under the pass: keeps what was read
                d_ns = cpu - ent[2]
                ent[2] = cpu
                tid = th.ident
                span = spans.get(tid)
                if not credit or (d_ns <= 0 and span is None):
                    continue
                role = ent[0]
                wait_ns = 0 if span is None else dt_ns - d_ns
                span = span or "-"
                self._charge(self._role_stats, role, d_ns, wait_ns)
                self._charge(self._span_stats, span, d_ns, wait_ns)
                if d_ns <= 0:
                    continue
                busy_any = busy_any or tid != me
                self.busy_ns += d_ns
                ent[3] += d_ns
                if ent[3] < WALK_NS:
                    continue
                d_ns, ent[3] = ent[3], 0
                frames = frames or sys._current_frames()
                f = frames.get(tid)
                if f is None:
                    continue  # ended since its clock was read
                descs = []  # leaf first
                while f is not None and len(descs) < self.max_depth:
                    c = f.f_code
                    cc = code_cache.get(c)
                    if cc is None:
                        cc = self._describe(c)
                        if len(code_cache) < 8192:
                            code_cache[c] = cc
                    descs.append(cc)
                    f = f.f_back
                key = (role, span, descs[0][1])
                site = self._sites.get(key)
                if site is None:
                    if len(self._sites) >= self.max_sites:
                        key = (role, span, OTHER_SITE)
                        self.sites_evicted += 1
                        site = self._sites.get(key)
                    if site is None:
                        site = self._sites[key] = [0, 0]
                site[0] += 1
                site[1] += d_ns
                # source dimension (clusterobs thread registry): only
                # threads currently serving an attributed request carry
                # one — handler CPU lands on its source node/namespace
                src = sources.get(tid)
                if src is not None:
                    if (
                        src not in self._source_ns
                        and len(self._source_ns) >= self.max_sources
                    ):
                        src = OTHER_SITE
                    self._source_ns[src] = (
                        self._source_ns.get(src, 0) + d_ns
                    )
                # collapsed stack (flamegraph surface): root-first,
                # weighted by CPU microseconds
                descs.reverse()
                stack_key = f"{role};{span};" + ";".join(
                    [d[0] for d in descs]
                )
                cnt = self._stacks.get(stack_key)
                if cnt is None:
                    if len(self._stacks) >= self.max_stacks:
                        self.stacks_dropped += 1
                        continue
                    cnt = 0
                self._stacks[stack_key] = cnt + max(1, d_ns // 1000)
            if len(ledger) > len(threads) or self._gone:
                live = set(threads)
                for th in [k for k in ledger if k not in live]:
                    del ledger[th]
                self._gone &= live
            if credit:
                self.idle_samples += not busy_any
                # the account closes: the process's CPU less every
                # role's is native threads (XLA/PJRT) and what was missed
                self._role_stats[UNACCOUNTED] = [
                    0, max(0, self._proc_ns - self.busy_ns), 0
                ]
        return busy_any

    @staticmethod
    def _charge(table: dict, key: str, cpu_ns: int, wait_ns: int = 0) -> None:
        ent = table.get(key)
        if ent is None:
            ent = table[key] = [0, 0, 0]
        ent[2] += wait_ns
        if cpu_ns > 0:
            ent[0] += 1
            ent[1] += cpu_ns

    @staticmethod
    def _describe(code) -> tuple[str, str]:
        """(frame label, leaf-site label) for one code object —
        computed once and cached; the per-sample path is dict hits."""
        fn = code.co_filename
        name = code.co_name
        if name == "_gc_cb" and fn.endswith("hostobs.py"):
            # gc.collect holds the GIL for the whole collection; the
            # sampler's only chance to run "inside" one is while the
            # Python gc callback executes, so the entire collection gap
            # lands on this frame — name it what it is
            return "(gc-collect)", "(gc-collect)"
        base = os.path.basename(fn)
        mod = base[:-3] if base.endswith(".py") else base
        return f"{mod}.{name}", f"{name} ({base}:{code.co_firstlineno})"

    # -- flush: buffered GC events + runtime gauges ----------------------

    def _flush(self) -> None:
        from . import metrics, trace as _trace

        with self._flush_lock:
            self._flush_locked(metrics, _trace)

    def _flush_locked(self, metrics, _trace) -> None:
        # drain the callback-side buffers (list slicing under the GIL;
        # the callback only appends)
        pending, self._gc_pending[:] = self._gc_pending[:], []
        sections, self._section_pending[:] = self._section_pending[:], []
        for gen, dt in pending:
            if 0 <= gen < 3:
                self.gc_collections[gen] += 1
            self.gc_pause_ns += dt
            if dt > self.gc_pause_max_ns:
                self.gc_pause_max_ns = dt
            metrics.incr("nomad.runtime.gc_collections")
            metrics.incr(f"nomad.runtime.gc_collections.gen{gen}")
            metrics.observe("nomad.runtime.gc_pause_seconds", dt / 1e9)
        if self.gc_dropped:
            metrics.incr("nomad.runtime.gc_pauses_dropped", self.gc_dropped)
            self.gc_dropped = 0
        collected_delta = self.gc_collected - self._gc_collected_flushed
        if collected_delta > 0:
            metrics.incr("nomad.runtime.gc_collected", collected_delta)
            self._gc_collected_flushed = self.gc_collected
        for dt in sections:
            self.gc_sections += 1
            self.gc_section_ns += dt
            metrics.incr("nomad.runtime.gc_paused_sections")
            metrics.observe(
                "nomad.runtime.gc_paused_section_seconds", dt / 1e9
            )
        # runtime gauges
        metrics.set_gauge(
            "nomad.runtime.threads", float(threading.active_count())
        )
        counts = gc.get_count()
        for gen in range(min(3, len(counts))):
            metrics.set_gauge(
                f"nomad.runtime.gc_pending.gen{gen}", float(counts[gen])
            )
        rss = _read_rss()
        if rss:
            metrics.set_gauge("nomad.runtime.rss_bytes", float(rss))
        fds = _count_fds()
        if fds is not None:
            metrics.set_gauge("nomad.runtime.fds", float(fds))
        # the cumulative sums, for an operator's scrape
        with self._lock:
            cpu = {r: s[1] for r, s in self._role_stats.items()}
            waits = {k: s[2] for k, s in self._span_stats.items() if k != "-"}
        for role, ns in cpu.items():
            metrics.set_gauge(f"nomad.host.cpu_seconds.{role}", ns / 1e9)
        for span, ns in waits.items():
            metrics.set_gauge(
                f"nomad.host.wait_seconds.{span}", max(0, ns) / 1e9
            )
        # prune the trace-side span registry + the clusterobs source
        # registry for dead tids
        live = {t.ident for t in threading.enumerate()}
        _trace.prune_thread_spans(live)
        from . import clusterobs as _clusterobs

        _clusterobs.prune_thread_sources(live)

    def _provider(self) -> dict:
        wall = max(1, now_ns() - self._started_ns)
        return {
            "samples": float(self.samples),
            "idle_samples": float(self.idle_samples),
            "busy_seconds": round(self.busy_ns / 1e9, 3),
            "duty_cycle": round(self._sampler_ns / wall, 6),
            "interval_ms": round(self.interval_s * 1e3, 3),
            "sites": float(len(self._sites)),
            "sites_evicted": float(self.sites_evicted),
            "stacks": float(len(self._stacks)),
            "stacks_dropped": float(self.stacks_dropped),
        }

    # -- read side -------------------------------------------------------

    def snapshot(self, top: int = 50) -> dict:
        """The /v1/profile/status payload."""
        if _enabled:
            self._sample()
        try:
            self._flush()
        except Exception:
            pass
        with self._lock:
            sites = sorted(
                self._sites.items(), key=lambda kv: -kv[1][1]
            )[: max(1, top)]
            spans = {
                k: {
                    "cpu_seconds": round(v[1] / 1e9, 4),
                    "wait_seconds": round(max(0, v[2]) / 1e9, 4),
                }
                for k, v in sorted(
                    self._span_stats.items(), key=lambda kv: -kv[1][1]
                )
            }
            sources = {
                k: round(v / 1e9, 4)
                for k, v in sorted(
                    self._source_ns.items(), key=lambda kv: -kv[1]
                )[: max(1, top)]
            }
            roles = {
                r: {
                    "samples": s[0],
                    "busy_seconds": round(s[1] / 1e9, 4),
                    "wait_seconds": round(max(0, s[2]) / 1e9, 4),
                }
                for r, s in sorted(self._role_stats.items())
            }
            wall_ns = max(1, now_ns() - self._started_ns)
            out = {
                "enabled": _enabled,
                "running": self.running(),
                "interval_ms": round(self.interval_s * 1e3, 3),
                "window_seconds": round(wall_ns / 1e9, 3),
                "samples": self.samples,
                "idle_samples": self.idle_samples,
                "busy_seconds": round(self.busy_ns / 1e9, 4),
                "overhead": {
                    "sampler_seconds": round(self._sampler_ns / 1e9, 4),
                    "duty_cycle": round(self._sampler_ns / wall_ns, 6),
                },
                "top_sites": [
                    {
                        "role": role,
                        "span": span,
                        "site": site,
                        "samples": ent[0],
                        "seconds": round(ent[1] / 1e9, 4),
                    }
                    for (role, span, site), ent in sites
                ],
                "spans": spans,
                # handler CPU x source (clusterobs dimension): CPU
                # seconds charged while the thread was serving an
                # attributed request for that source
                "sources": sources,
                "threads": roles,
                "sites": len(self._sites),
                "sites_evicted": self.sites_evicted,
                "stacks": len(self._stacks),
                "stacks_dropped": self.stacks_dropped,
                "gc": {
                    "collections": {
                        f"gen{i}": n
                        for i, n in enumerate(self.gc_collections)
                    },
                    "collected": self.gc_collected,
                    "pause_seconds_total": round(self.gc_pause_ns / 1e9, 6),
                    "pause_max_s": round(self.gc_pause_max_ns / 1e9, 6),
                    "paused_sections": self.gc_sections,
                    "paused_section_seconds": round(
                        self.gc_section_ns / 1e9, 6
                    ),
                },
                "locks": lock_stats(),
                "runtime": {
                    "rss_bytes": _read_rss(),
                    "threads": threading.active_count(),
                    "fds": _count_fds(),
                    "gc_pending": list(gc.get_count()),
                },
            }
        return out

    def collapsed(self, limit: int = 0) -> str:
        """Collapsed-stack text (``role;span;frame;...;leaf count`` per
        line, Brendan-Gregg format): feed to flamegraph.pl / speedscope
        / inferno verbatim. Sorted by sample count, heaviest first."""
        with self._lock:
            items = sorted(self._stacks.items(), key=lambda kv: -kv[1])
        if limit > 0:
            items = items[:limit]
        return "\n".join(f"{stack} {count}" for stack, count in items) + (
            "\n" if items else ""
        )


def _read_rss() -> int:
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        pass
    return 0


def _count_fds() -> Optional[int]:
    try:
        return len(os.listdir("/proc/self/fd"))
    except OSError:
        return None


# -- process-global instance + module conveniences -----------------------

_global = HostProfiler()


def profiler() -> HostProfiler:
    return _global


def _install(prof: HostProfiler) -> HostProfiler:
    """Swap the process-global profiler (returns the previous one) —
    the test isolation hook, mirroring solverobs._install. The caller
    owns stopping the old instance's thread if it started one."""
    global _global, start, stop, running, configure, reset_stats
    global snapshot, collapsed, note_gc_section, note_thread_exit
    old = _global
    _global = prof
    start = prof.start
    stop = prof.stop
    running = prof.running
    configure = prof.configure
    reset_stats = prof.reset_stats
    snapshot = prof.snapshot
    collapsed = prof.collapsed
    note_gc_section = prof.note_gc_section
    note_thread_exit = prof.note_thread_exit
    return old


start = _global.start
stop = _global.stop
running = _global.running
configure = _global.configure
reset_stats = _global.reset_stats
snapshot = _global.snapshot
collapsed = _global.collapsed
note_gc_section = _global.note_gc_section
note_thread_exit = _global.note_thread_exit
