"""Evaluation broker: leader-only priority queue of pending evaluations.

Reference: nomad/eval_broker.go (901 LoC) — Enqueue :181, Dequeue :329,
Ack :531, Nack :595, delayed-eval heap :751, PendingEvaluations :861.

Semantics preserved:
  * per-scheduler-type priority heaps (workers dequeue only the types they
    run; the TPU batch worker dequeues many at once);
  * per-job serialization — at most ONE eval per (namespace, job) in flight;
    later evals for the same job wait in a per-job heap and are promoted on
    ack of the previous one;
  * ack/nack with a delivery limit: nacked evals re-enqueue after a delay,
    over-limit evals land in the failed queue;
  * delayed evals (wait_until in the future) sit in a time heap serviced by
    a timer thread.

Admission control (overload protection — the reference broker is
unbounded and relies on endpoint limits alone; a batched TPU solver
makes a bounded backlog mandatory because one mega-batch stall backs up
the whole pipeline):
  * ``admission_depth`` bounds the PENDING population (ready + per-job
    waiters + delayed; unacked in-flight evals are excluded). Past the
    depth an arriving eval is admitted only by displacing something:
    first an older duplicate waiting behind the same job (newest eval
    carries the freshest trigger — the state store cancels older
    pending evals on upsert the same way), else the lowest-priority
    pending eval strictly below the newcomer's priority. Otherwise the
    newcomer itself is shed.
  * ``namespace_cap`` is a per-namespace fairness bound: one namespace
    cannot occupy more than this many pending slots no matter how far
    below admission_depth the broker sits.
  * Every shed increments ``nomad.broker.shed`` (+ a per-reason
    counter) and finishes the eval's trace as "shed". A shed eval's
    state-store record stays pending: the next leadership restore or a
    superseding eval for the same job re-covers the work — shedding
    sheds BROKER load, never acked writes.

Shedding engages only when the knobs are set (depth 0 = unbounded, the
seed default), so an unconfigured broker behaves exactly as before.
Redeliveries (nack → delay → requeue) bypass admission: an eval that
was admitted once is never rejected at the door and never chosen as a
priority-displacement victim (it carries a live attempt count, which
keeps it out of the pending index). The one way a redelivery can still
leave early is DUPLICATE displacement — a newer eval for the same job
superseding it — which is safe by the same argument as the state
store's cancel-on-upsert: the newest eval re-covers the job's work.
"""

from __future__ import annotations

import heapq
import itertools
import threading
import time
from typing import Optional

from .. import blackbox, metrics, trace
from ..structs import Evaluation, generate_uuid, now_ns

DEFAULT_NACK_DELAY_S = 5.0
DEFAULT_DELIVERY_LIMIT = 3
FAILED_QUEUE = "_failed"


# Shared free-list cap for pooled 3-slot heap/unacked entries. At
# steady state every enqueue->dequeue->ack cycle recycles its entry
# instead of minting a tuple per hop; the cap bounds the pool after a
# backlog drains.
_ENTRY_POOL_CAP = 4096


class _PendingHeap:
    """Priority heap: higher priority first, then FIFO. ``dropped`` is
    the broker's shared tombstone set (admission-control evictions):
    entries whose eval id is in it are discarded lazily at pop/peek —
    heap surgery without O(n) re-heapify on the enqueue hot path.

    Entries are POOLED 3-slot lists ([-priority, seq, eval]) drawn from
    the broker's shared free list (``pool``): lists compare elementwise
    exactly like the tuples they replace, and recycling them at pop
    kills the per-eval entry allocation on the enqueue->dequeue path."""

    def __init__(
        self,
        dropped: Optional[set] = None,
        pool: Optional[list] = None,
    ) -> None:
        self._heap: list = []
        self._counter = itertools.count()
        self._dropped = dropped if dropped is not None else set()
        self._pool = pool if pool is not None else []

    def _entry(self, ev: Evaluation) -> list:
        pool = self._pool
        if pool:
            e = pool.pop()
            e[0] = -ev.priority
            e[1] = next(self._counter)
            e[2] = ev
            return e
        return [-ev.priority, next(self._counter), ev]

    def _recycle(self, entry: list) -> None:
        if len(self._pool) < _ENTRY_POOL_CAP:
            entry[2] = None
            self._pool.append(entry)

    def push(self, ev: Evaluation) -> None:
        heapq.heappush(self._heap, self._entry(ev))

    def push_all(self, evs: list) -> None:
        """Bulk admission: append pooled entries for the whole batch and
        heapify ONCE (O(n)) instead of sifting per push — the
        enqueue_all fast path."""
        heap = self._heap
        for ev in evs:
            heap.append(self._entry(ev))
        if len(heap) > 1:
            heapq.heapify(heap)

    def pop(self) -> Optional[Evaluation]:
        while self._heap:
            entry = heapq.heappop(self._heap)
            ev = entry[2]
            self._recycle(entry)
            if ev.id in self._dropped:
                self._dropped.discard(ev.id)
                continue
            return ev
        return None

    def peek(self) -> Optional[Evaluation]:
        while self._heap:
            ev = self._heap[0][2]
            if ev.id not in self._dropped:
                return ev
            self._recycle(heapq.heappop(self._heap))
            self._dropped.discard(ev.id)
        return None

    def oldest_waiter_below(self, priority: int) -> Optional[Evaluation]:
        """The oldest (smallest seq) live entry with priority <= the
        given one — the duplicate-shed victim. O(n) over this JOB's
        waiters only (bounded by per-job churn, not queue depth)."""
        best = None
        for _negp, seq, ev in self._heap:
            if ev.id in self._dropped or ev.priority > priority:
                continue
            if best is None or seq < best[0]:
                best = (seq, ev)
        return best[1] if best else None

    def __len__(self) -> int:
        return len(self._heap)


class EvalBroker:
    def __init__(
        self,
        nack_delay_s: float = DEFAULT_NACK_DELAY_S,
        delivery_limit: int = DEFAULT_DELIVERY_LIMIT,
        admission_depth: int = 0,
        namespace_cap: int = 0,
    ) -> None:
        self.nack_delay_s = nack_delay_s
        self.delivery_limit = delivery_limit
        # Admission knobs (0 = unbounded): see the module docstring.
        self.admission_depth = admission_depth
        self.namespace_cap = namespace_cap
        # Lock-wait-attributed (hostobs.TimedLock): every enqueue/
        # dequeue/ack/nack from every worker serializes here — the lock
        # the "GC-bound vs lock-bound vs materialize-bound" runbook
        # triage reads first (docs/operations.md). Uncontended cost is
        # one extra non-blocking try-acquire.
        from ..hostobs import TimedLock

        self._lock = TimedLock("broker", threading.RLock())
        self._cv = threading.Condition(self._lock)
        self._enabled = False
        # Tombstones for admission-control evictions: ids whose heap
        # entries are discarded lazily at the pop sites (ready heaps,
        # per-job waiter heaps, the delayed list).
        self._dropped: set[str] = set()
        # Pending-population index: eval id -> the broker's Evaluation
        # copy, for every PENDING eval (ready / waiting behind its job /
        # delayed; NOT unacked). The admission depth bounds len() of
        # this dict; the priority buckets make the lowest-priority
        # victim an O(priority-range) lookup instead of an O(depth)
        # scan, and holding the full eval lets a shed victim release
        # its job's in-flight slot correctly.
        self._pending_info: dict[str, Evaluation] = {}
        self._ns_pending: dict[str, int] = {}
        # priority -> insertion-ordered {eval_id: None} (FIFO within a
        # priority level, so the victim is the OLDEST at the lowest
        # priority)
        self._prio_buckets: dict[int, dict[str, None]] = {}
        self.shed_total = 0
        # Shared free list of pooled 3-slot entries, recycled across
        # every ready/waiter heap AND the unacked records: the
        # enqueue->dequeue->ack cycle reuses one list instead of
        # allocating a heap tuple at enqueue plus an unacked tuple at
        # dequeue per eval.
        self._entry_pool: list = []
        # scheduler type -> ready heap
        self._ready: dict[str, _PendingHeap] = {}
        # eval id -> [eval, token, attempts] for unacked evals (pooled
        # 3-slot lists from _entry_pool, returned at ack/nack)
        self._unacked: dict[str, list] = {}
        # (ns, job) -> in-flight eval id
        self._in_flight: dict[tuple[str, str], str] = {}
        # (ns, job) -> heap of evals waiting behind the in-flight one
        self._blocked_jobs: dict[tuple[str, str], _PendingHeap] = {}
        # delayed evals: (wait_until_ns, seq, eval)
        self._delayed: list = []
        self._delayed_counter = itertools.count()
        self._attempts: dict[str, int] = {}  # eval id -> deliveries
        # eval id -> (TraceContext, open Span) — the per-eval lifecycle
        # trace started at enqueue (trace.py). Bounded by queue depth:
        # entries leave at ack / dead-letter / flush.
        self._traces: dict[str, tuple] = {}
        # eval id -> monotonic FIRST-enqueue time: the basis of
        # nomad.eval.e2e_seconds, observed at ack (the worker acks only
        # after the plan is applied). setdefault keeps the original
        # enqueue across nack redeliveries so redelivered evals report
        # their true end-to-end time. Bounded like _traces: entries
        # leave at ack / dead-letter / flush.
        self._enqueue_times: dict[str, float] = {}
        # eval id -> monotonic time it last became READY (pushed onto a
        # ready heap): the basis of nomad.broker.wait_seconds at
        # dequeue. Distinct from _enqueue_times on purpose — a
        # redelivered eval's queue wait must not include the prior
        # attempt's processing time or the nack delay.
        self._wait_starts: dict[str, float] = {}
        # (min_priority, event): set when an eval at or above that
        # priority becomes ready (watch_ready)
        self._ready_watch: list[tuple[int, threading.Event]] = []
        self._timer: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self.stats = {
            "total_ready": 0,
            "total_unacked": 0,
            "total_blocked": 0,
            "total_waiting": 0,
            "failed": 0,
        }

    # -- configuration --------------------------------------------------

    def configure(
        self,
        nack_delay_s: Optional[float] = None,
        delivery_limit: Optional[int] = None,
        admission_depth: Optional[int] = None,
        namespace_cap: Optional[int] = None,
    ) -> None:
        """Live reconfiguration (agent SIGHUP reload): every knob applies
        to the running broker without a flush — in-flight deliveries
        keep their attempt counts, pending evals stay queued."""
        with self._lock:
            if nack_delay_s is not None:
                self.nack_delay_s = float(nack_delay_s)
            if delivery_limit is not None:
                self.delivery_limit = int(delivery_limit)
            if admission_depth is not None:
                self.admission_depth = int(admission_depth)
            if namespace_cap is not None:
                self.namespace_cap = int(namespace_cap)

    # -- lifecycle -----------------------------------------------------

    def set_enabled(self, enabled: bool) -> None:
        with self._lock:
            was = self._enabled
            self._enabled = enabled
            if was and not enabled:
                self._flush_locked()
            if not was and enabled:
                self._stop.clear()
                self._timer = threading.Thread(
                    target=self._delayed_loop, daemon=True, name="broker-delayed"
                )
                self._timer.start()
            self._cv.notify_all()
        if was and not enabled:
            self._stop.set()

    @property
    def enabled(self) -> bool:
        return self._enabled

    def watch_ready(self, min_priority: int, event: threading.Event) -> None:
        """Set `event` whenever an eval at or above `min_priority`
        becomes ready: a worker blocked on something else (a commit)
        wakes to serve it (TPUBatchWorker's interactive lane)."""
        with self._lock:
            self._ready_watch.append((min_priority, event))

    def unwatch_ready(self, event: threading.Event) -> None:
        with self._lock:
            self._ready_watch = [
                w for w in self._ready_watch if w[1] is not event
            ]

    def _flush_locked(self) -> None:
        self._ready.clear()
        self._unacked.clear()
        self._in_flight.clear()
        self._blocked_jobs.clear()
        self._delayed.clear()
        # _attempts SURVIVES the flush on purpose: leadership often
        # bounces straight back to this node (restart churn), and a
        # redelivered eval must keep its delivery count or the
        # delivery_limit resets on every churn — a poison eval could
        # then loop forever instead of dead-lettering. Entries still
        # clear at ack/dead-letter; the cap guards pathological churn
        # where evals are acked on OTHER nodes and never clear here.
        # The eviction keeps counts for ids the broker still TRACKS
        # (_enqueue_times, cleared below, is exactly that set at this
        # point): a blanket clear() zeroed live in-flight evals'
        # delivery counts too, letting a poison eval dodge the
        # delivery_limit across every leadership bounce.
        if len(self._attempts) > 8192:
            tracked = self._enqueue_times
            self._attempts = {
                k: v for k, v in self._attempts.items() if k in tracked
            }
        # leadership loss: in-flight traces are abandoned, not recorded
        self._traces.clear()
        self._enqueue_times.clear()
        self._wait_starts.clear()
        self._dropped.clear()
        self._pending_info.clear()
        self._ns_pending.clear()
        self._prio_buckets.clear()

    # -- enqueue -------------------------------------------------------

    def enqueue(self, ev: Evaluation) -> None:
        with self._lock:
            self._enqueue_locked(ev.copy())

    def enqueue_all(self, evals: list[Evaluation]) -> None:
        """Batch enqueue: one lock acquisition for the whole batch, one
        timestamp read, one condition broadcast, and bulk per-type heap
        admission (append + single heapify) instead of a per-eval
        sift — the TPU batch producer's hot path. Admission control,
        per-job serialization, delayed evals, and traces run the exact
        per-eval logic `enqueue` does; only the ready-heap insertion
        and the wakeup are batched."""
        if not evals:
            return
        with self._lock:
            if not self._enabled:
                return
            bulk: dict[str, list] = {}
            now_mono = time.monotonic()
            for ev in evals:
                self._enqueue_locked(ev.copy(), bulk=bulk, now_mono=now_mono)
            for stype, ready in bulk.items():
                self._ready.setdefault(stype, self._heap()).push_all(ready)
            if bulk:
                self._cv.notify_all()

    # -- admission accounting -------------------------------------------

    def _pending_add(self, ev: Evaluation) -> None:
        if ev.id in self._pending_info:
            return
        if self._attempts.get(ev.id):
            # A redelivery (delivered at least once, nacked, waiting or
            # re-promoted): it was admitted when it first arrived, so it
            # neither counts against the admission depth nor enters the
            # displacement victim pool. Shedding a mid-retry eval would
            # break its e2e accounting and — worse, in the delay heap —
            # strand the job's queued waiters: its in-flight marker was
            # already cleared at nack, so _shed_locked would have no
            # slot to release and nothing would ever promote them.
            return
        self._pending_info[ev.id] = ev
        self._ns_pending[ev.namespace] = (
            self._ns_pending.get(ev.namespace, 0) + 1
        )
        self._prio_buckets.setdefault(ev.priority, {})[ev.id] = None

    def _pending_remove(self, eval_id: str) -> None:
        ev = self._pending_info.pop(eval_id, None)
        if ev is None:
            return
        n = self._ns_pending.get(ev.namespace, 0) - 1
        if n > 0:
            self._ns_pending[ev.namespace] = n
        else:
            self._ns_pending.pop(ev.namespace, None)
        bucket = self._prio_buckets.get(ev.priority)
        if bucket is not None:
            bucket.pop(eval_id, None)
            if not bucket:
                del self._prio_buckets[ev.priority]

    def _shed_locked(self, ev: Evaluation, reason: str,
                     tracked: bool) -> None:
        """Drop one eval from the broker's books. ``tracked`` — it was
        admitted earlier (an evicted victim) vs an arriving eval that
        never entered."""
        self.shed_total += 1
        metrics.incr("nomad.broker.shed")
        metrics.incr(f"nomad.broker.shed.{reason}")
        blackbox.record(
            blackbox.KIND_SHED, f"eval:{ev.id}", reason=reason,
            tracked=tracked,
            rel=[f"eval:{ev.id}"] + (
                [f"job:{ev.job_id}"] if ev.job_id else []
            ),
        )
        if tracked:
            self._dropped.add(ev.id)
            self._pending_remove(ev.id)
            self._wait_starts.pop(ev.id, None)
            # a shed eval is no longer the job's in-flight marker: a
            # READY victim held the slot — promote the next waiter so
            # the job never strands behind a tombstone
            key = (ev.namespace, ev.job_id)
            if ev.job_id and self._in_flight.get(key) == ev.id:
                self._release_job_locked(ev, ev.id)
        self._enqueue_times.pop(ev.id, None)
        tentry = self._traces.pop(ev.id, None)
        if tentry is not None:
            ctx, open_span = tentry
            open_span.attrs = dict(
                open_span.attrs or {}, outcome="shed", reason=reason
            )
            ctx.end_span(open_span)
            ctx.finish("shed")

    def _victim_below_locked(self, priority: int) -> Optional[Evaluation]:
        """Oldest pending eval at the lowest priority strictly below
        the given one (None when nothing qualifies)."""
        for prio in sorted(self._prio_buckets):
            if prio >= priority:
                return None
            bucket = self._prio_buckets[prio]
            if bucket:
                return self._pending_info[next(iter(bucket))]
        return None

    def _admit_locked(self, ev: Evaluation) -> bool:
        """Admission decision for a NEW enqueue. True = admitted (a
        duplicate or lower-priority victim may have been evicted to
        make room); False = shed the arrival."""
        if self.admission_depth <= 0 and self.namespace_cap <= 0:
            return True
        if ev.type == "_core" or ev.id in self._enqueue_times:
            # GC/core evals are leader-internal and tiny; a re-enqueue
            # of an id the broker already tracks (pending OR unacked)
            # must not double-count or shed the live eval's bookkeeping
            return True
        pending = len(self._pending_info)
        ns_full = (
            self.namespace_cap > 0
            and self._ns_pending.get(ev.namespace, 0) >= self.namespace_cap
        )
        depth_full = (
            self.admission_depth > 0 and pending >= self.admission_depth
        )
        if not ns_full and not depth_full:
            return True
        # 1) duplicate displacement: the job already has waiters — the
        # oldest duplicate at <= priority yields its slot to the newest
        # trigger (works for both the depth and the namespace bound,
        # since the duplicate shares the namespace)
        key = (ev.namespace, ev.job_id)
        waiters = self._blocked_jobs.get(key) if ev.job_id else None
        if waiters is not None:
            dup = waiters.oldest_waiter_below(ev.priority)
            if dup is not None:
                self._shed_locked(dup, "duplicate", tracked=True)
                return True
        if ns_full:
            # fairness cap: no cross-namespace eviction — the newcomer's
            # own namespace is over budget, so it is the one shed
            self._shed_locked(ev, "namespace", tracked=False)
            return False
        # 2) priority displacement: evict the oldest lowest-priority
        # pending eval strictly below the newcomer. The victim may be
        # READY and holding its job's in-flight slot — _shed_locked
        # releases it and promotes the next waiter, so the job never
        # strands behind a tombstone.
        victim = self._victim_below_locked(ev.priority)
        if victim is not None:
            self._shed_locked(victim, "depth", tracked=True)
            return True
        self._shed_locked(ev, "depth", tracked=False)
        return False

    def _enqueue_locked(
        self,
        ev: Evaluation,
        bulk: Optional[dict] = None,
        now_mono: Optional[float] = None,
    ) -> None:
        if not self._enabled:
            return
        if not self._admit_locked(ev):
            return
        if now_mono is None:
            now_mono = time.monotonic()
        self._enqueue_times.setdefault(ev.id, now_mono)
        if trace.enabled() and ev.id not in self._traces:
            ctx = trace.start_trace(
                "eval",
                eval_id=ev.id,
                job_id=ev.job_id,
                type=ev.type,
                triggered_by=ev.triggered_by,
            )
            if ctx is not None:
                self._traces[ev.id] = (
                    ctx,
                    ctx.start_span("broker.wait", detached=True),
                )
        if ev.wait_until_ns and ev.wait_until_ns > now_ns():
            self._pending_add(ev)
            heapq.heappush(
                self._delayed, (ev.wait_until_ns, next(self._delayed_counter), ev)
            )
            self._cv.notify_all()
            return
        key = (ev.namespace, ev.job_id)
        if ev.job_id and key in self._in_flight:
            self._pending_add(ev)
            self._blocked_jobs.setdefault(key, self._heap()).push(ev)
            return
        self._push_ready(ev, bulk=bulk, now_mono=now_mono)

    def _heap(self) -> _PendingHeap:
        """A heap sharing the broker's admission tombstone set and
        pooled-entry free list."""
        return _PendingHeap(self._dropped, self._entry_pool)

    def _push_ready(
        self,
        ev: Evaluation,
        bulk: Optional[dict] = None,
        now_mono: Optional[float] = None,
    ) -> None:
        self._pending_add(ev)
        self._wait_starts[ev.id] = (
            now_mono if now_mono is not None else time.monotonic()
        )
        if ev.job_id:
            self._in_flight[(ev.namespace, ev.job_id)] = ev.id
        for min_priority, event in self._ready_watch:
            if ev.priority >= min_priority:
                event.set()
        if bulk is not None:
            # enqueue_all collects per-type lists; the caller bulk-pushes
            # each heap once and broadcasts once after the loop
            bulk.setdefault(ev.type, []).append(ev)
            return
        self._ready.setdefault(ev.type, self._heap()).push(ev)
        self._cv.notify_all()

    # -- dequeue / ack / nack -----------------------------------------

    def dequeue(
        self, schedulers: list[str], timeout_s: Optional[float] = None
    ) -> tuple[Optional[Evaluation], str]:
        """Blocking dequeue of the highest-priority ready eval among the
        given scheduler types. Returns (eval, token) or (None, "")."""
        ev, token, _ready = self.dequeue_ready(schedulers, timeout_s)
        return ev, token

    def dequeue_ready(
        self, schedulers: list[str], timeout_s: Optional[float] = None,
        min_priority: int = 0,
    ) -> tuple[Optional[Evaluation], str, int]:
        """`dequeue` of an eval at or above `min_priority`, with the
        instant it became ready on the trace clock (`trace.now_ns`; 0
        when unknown). Returns (eval, token, ready_ns) or (None, "", 0)."""
        deadline = time.monotonic() + timeout_s if timeout_s is not None else None
        while True:
            wait_s = None
            ready_ns = 0
            with self._cv:
                if self._enabled:
                    ev = self._pop_best_locked(schedulers, min_priority)
                    if ev is not None:
                        # pending -> in-flight: the admission bound
                        # covers the backlog, not work being processed
                        self._pending_remove(ev.id)
                        # per-dequeue token: generate_uuid serves from
                        # the bulk-minted pool (one generate_uuids(256)
                        # pass per 256 ids — no per-dequeue entropy
                        # syscall or format work)
                        token = generate_uuid()
                        attempts = self._attempts.get(ev.id, 0) + 1
                        self._attempts[ev.id] = attempts
                        # pooled unacked record: reuse a free 3-slot
                        # entry instead of minting a tuple per delivery
                        pool = self._entry_pool
                        rec = pool.pop() if pool else [None, None, None]
                        rec[0], rec[1], rec[2] = ev, token, attempts
                        self._unacked[ev.id] = rec
                        ready_at = self._wait_starts.pop(ev.id, None)
                        if ready_at is not None:
                            wait_s = time.monotonic() - ready_at
                            # time.monotonic is the trace clock's base
                            ready_ns = int(ready_at * 1e9)
                        entry = self._traces.get(ev.id)
                        if entry is not None:
                            ctx, open_span = entry
                            ctx.end_span(open_span)
                            # NOT detached: dequeue runs on the worker's
                            # own thread, so the processing span rides
                            # that thread's stack and the worker's
                            # snapshot/scheduler/plan spans nest under it
                            self._traces[ev.id] = (
                                ctx,
                                ctx.start_span(
                                    "processing",
                                    parent=ctx.root,
                                    attempt=attempts,
                                ),
                            )
                        break
                if deadline is not None:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        return None, "", 0
                    self._cv.wait(remaining)
                else:
                    self._cv.wait(1.0)
        # histogram observe OUTSIDE the broker lock: the registry has
        # its own lock and nesting it under _cv would add a lock-order
        # edge the racecheck battery would have to carry forever
        if wait_s is not None:
            metrics.observe("nomad.broker.wait_seconds", wait_s)
        return ev, token, ready_ns

    def _pop_best_locked(
        self, schedulers: list[str], min_priority: int = 0
    ) -> Optional[Evaluation]:
        best_type = None
        best = None
        for stype in schedulers:
            heap = self._ready.get(stype)
            if heap is None:
                continue
            ev = heap.peek()
            if ev is None:
                continue
            if best is None or ev.priority > best.priority:
                best, best_type = ev, stype
        if best is None or best.priority < min_priority:
            return None
        return self._ready[best_type].pop()

    def ack(self, eval_id: str, token: str) -> None:
        with self._lock:
            entry = self._unacked.get(eval_id)
            if entry is None or entry[1] != token:
                raise ValueError(f"token mismatch or unknown eval {eval_id}")
            del self._unacked[eval_id]
            ev = entry[0]
            if len(self._entry_pool) < _ENTRY_POOL_CAP:
                entry[0] = entry[1] = entry[2] = None
                self._entry_pool.append(entry)
            self._attempts.pop(eval_id, None)
            self._release_job_locked(ev, eval_id)
            tentry = self._traces.pop(eval_id, None)
            enq = self._enqueue_times.pop(eval_id, None)
        if enq is not None:
            # ack lands only after the eval's plan was applied (workers
            # ack post-commit), so this IS the end-to-end eval latency:
            # broker enqueue -> plan applied. One aggregate histogram
            # plus a per-(scheduler type, triggered-by) labelled one —
            # both label sets are small and closed.
            e2e = time.monotonic() - enq
            metrics.observe("nomad.eval.e2e_seconds", e2e)
            metrics.observe(
                f"nomad.eval.e2e_seconds.{ev.type}"
                f".{ev.triggered_by or 'unknown'}",
                e2e,
            )
        if tentry is not None:
            ctx, open_span = tentry
            ctx.end_span(open_span)
            ctx.finish("ok")

    def nack(self, eval_id: str, token: str) -> None:
        with self._lock:
            entry = self._unacked.get(eval_id)
            if entry is None or entry[1] != token:
                raise ValueError(f"token mismatch or unknown eval {eval_id}")
            del self._unacked[eval_id]
            ev, _, attempts = entry
            if len(self._entry_pool) < _ENTRY_POOL_CAP:
                entry[0] = entry[1] = entry[2] = None
                self._entry_pool.append(entry)
            key = (ev.namespace, ev.job_id)
            if attempts >= self.delivery_limit:
                # dead-letter: failed queue for the reaper; the job's waiting
                # evals must still be promoted or they strand forever
                self._attempts.pop(eval_id, None)
                self._release_job_locked(ev, eval_id)
                self._ready.setdefault(FAILED_QUEUE, self._heap()).push(ev)
                self.stats["failed"] += 1
                self._cv.notify_all()
                self._enqueue_times.pop(eval_id, None)
                tentry = self._traces.pop(eval_id, None)
                if tentry is not None:
                    ctx, open_span = tentry
                    open_span.attrs = dict(open_span.attrs or {},
                                           outcome="nack")
                    ctx.end_span(open_span)
                    ctx.finish("failed")
                return
            if self._in_flight.get(key) == eval_id:
                del self._in_flight[key]
            tentry = self._traces.get(eval_id)
            if tentry is not None:
                ctx, open_span = tentry
                open_span.attrs = dict(open_span.attrs or {}, outcome="nack")
                ctx.end_span(open_span)
                self._traces[eval_id] = (
                    ctx,
                    ctx.start_span(
                        "nack.wait", parent=ctx.root, detached=True
                    ),
                )
            # re-enqueue after the nack delay. Redeliveries bypass
            # admission entirely — _pending_add refuses ids with a live
            # attempt count, so a retry is never rejected at the door
            # NOR chosen as a displacement victim while it waits.
            requeue_at = now_ns() + int(self.nack_delay_s * 1e9)
            heapq.heappush(
                self._delayed, (requeue_at, next(self._delayed_counter), ev)
            )
            self._cv.notify_all()

    def _release_job_locked(self, ev: Evaluation, eval_id: str) -> None:
        """Clear the job's in-flight marker and promote the next waiter."""
        key = (ev.namespace, ev.job_id)
        if self._in_flight.get(key) == eval_id:
            del self._in_flight[key]
        blocked = self._blocked_jobs.get(key)
        if blocked:
            nxt = blocked.pop()
            if len(blocked) == 0:
                del self._blocked_jobs[key]
            if nxt is not None:
                self._push_ready(nxt)

    # -- delayed servicing --------------------------------------------

    def _delayed_loop(self) -> None:
        while not self._stop.is_set():
            with self._cv:
                now = now_ns()
                while self._delayed and self._delayed[0][0] <= now:
                    _, _, ev = heapq.heappop(self._delayed)
                    if ev.id in self._dropped:
                        # admission-control eviction landed while the
                        # eval sat in the delay heap
                        self._dropped.discard(ev.id)
                        continue
                    key = (ev.namespace, ev.job_id)
                    if ev.job_id and key in self._in_flight:
                        self._blocked_jobs.setdefault(key, self._heap()).push(ev)
                    else:
                        self._push_ready(ev)
                wait = 0.2
                if self._delayed:
                    wait = min(wait, max(0.0, (self._delayed[0][0] - now) / 1e9))
            self._stop.wait(wait)

    # -- introspection -------------------------------------------------

    def pending_count(self) -> int:
        """Admitted-but-undelivered evals (ready + per-job waiters +
        delayed) — the population admission_depth bounds."""
        with self._lock:
            return len(self._pending_info)

    def namespace_pending(self, namespace: str) -> int:
        with self._lock:
            return self._ns_pending.get(namespace, 0)

    def saturation(self, namespace: str = "") -> Optional[tuple[str, float]]:
        """Front-door admission probe: (reason, retry_after_s) when a
        new eval for this namespace would be rejected outright — the
        leader's eval-minting write endpoints call this BEFORE raft so
        overload surfaces as 429 instead of a shed after commit. None
        while there is room (or admission is unconfigured/disabled).

        Saturated means even displacement cannot help an average-
        priority arrival: pending >= depth with nothing obviously
        evictable is approximated as pending >= depth (the per-eval
        displacement still runs for internal producers; the front door
        is simply told to back off first — the reference's posture of
        rejecting at the edge before queueing in the core). The hint
        scales with how far past the bound the backlog sits."""
        with self._lock:
            if not self._enabled:
                return None
            if (
                self.namespace_cap > 0
                and namespace
                and self._ns_pending.get(namespace, 0) >= self.namespace_cap
            ):
                return ("namespace", self.nack_delay_s / 4)
            if self.admission_depth > 0:
                pending = len(self._pending_info)
                if pending >= self.admission_depth:
                    over = pending - self.admission_depth
                    return (
                        "depth",
                        min(5.0, 0.5 + over / max(1, self.admission_depth)),
                    )
        return None

    def stats_snapshot(self) -> dict:
        """Live queue depths + shed counters for the metrics provider.
        (The legacy ``stats`` dict only ever tracked dead-letters; these
        gauges are computed from the real structures under the lock so
        `operator top` shows true depths.)"""
        with self._lock:
            ready = sum(
                len(h) for t, h in self._ready.items() if t != FAILED_QUEUE
            )
            waiters = sum(len(h) for h in self._blocked_jobs.values())
            return {
                "total_ready": ready,
                "total_unacked": len(self._unacked),
                "total_blocked": waiters,
                "total_waiting": len(self._delayed),
                "total_pending": len(self._pending_info),
                "total_shed": self.shed_total,
                "admission_depth": self.admission_depth,
                "namespace_cap": self.namespace_cap,
                "failed": self.stats["failed"],
            }

    def tracks(self, eval_id: str) -> bool:
        """Is this eval currently anywhere in the broker (ready, unacked,
        waiting behind its job, or nack-delayed)? _enqueue_times is
        exactly that set: setdefault'ed on every enqueue, popped only at
        ack / dead-letter / flush. Used by the leader's _restore_evals
        so restoring state after churn is idempotent — an eval the FSM
        side-channel already enqueued is not enqueued again."""
        with self._lock:
            return eval_id in self._enqueue_times

    def trace_context(self, eval_id: str):
        """The in-flight eval's TraceContext (None when untracked): the
        worker installs it as the thread's current context so scheduler
        and plan spans land on the eval's own trace."""
        with self._lock:
            entry = self._traces.get(eval_id)
        return entry[0] if entry is not None else None

    def annotate_trace(self, eval_id: str, **attrs) -> None:
        """Attach attrs to an in-flight eval's trace (the TPU batch
        worker links each eval to its batch trace this way)."""
        with self._lock:
            entry = self._traces.get(eval_id)
        if entry is not None:
            for k, v in attrs.items():
                entry[0].set_attr(k, v)

    def ready_count(self) -> int:
        with self._lock:
            return sum(len(h) for t, h in self._ready.items() if t != FAILED_QUEUE)

    def inflight_count(self) -> int:
        with self._lock:
            return len(self._unacked)

    def outstanding(self, eval_id: str) -> bool:
        with self._lock:
            return eval_id in self._unacked
