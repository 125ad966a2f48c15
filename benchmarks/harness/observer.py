"""When is an allocation visible to its node?

The clock of every end-to-end metric stops when the allocation is
visible on its node's watch: the watch hub's per-node index has passed
the index at which the store committed the alloc. A real node learns
that on its blocking `Node.get_client_allocs`; 10,000 of those would
measure the host's thread scheduler. The observer is one thread that
does what such a watcher does, for every node in turn: it hears of a
commit from the store (a subscriber that only appends to a queue under
the store's lock), then blocks on the hub's own `wait_for_node` until
the hub has routed that commit to each node it touched. Event driven,
nothing polls.
"""

from __future__ import annotations

import threading
import time
from collections import deque


class JobWatch:
    """What one registered job is waited on for."""

    __slots__ = ("job_id", "asked", "t_sent", "t_acked", "seen", "commits",
                 "t_visible", "done", "eval_id", "error")

    def __init__(self, job_id: str, asked: int) -> None:
        self.job_id = job_id
        self.asked = asked
        self.t_sent = 0.0
        self.t_acked = 0.0
        self.seen: set[str] = set()  # alloc ids visible so far
        self.commits = 0  # plan results that carried allocs of this job
        self.t_visible = 0.0  # when the last of `asked` became visible
        self.done = threading.Event()
        self.eval_id = ""
        self.error = ""  # a definitive failure at the front door


class Observer:
    def __init__(self, state, hub, hang_s: float = 60.0) -> None:
        from nomad_tpu.state.store import TABLE_ALLOCS

        self._table = TABLE_ALLOCS
        self._hub = hub
        self._hang_s = hang_s
        self._inbox: deque = deque()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._jobs: dict[str, JobWatch] = {}
        # (seconds since the window's origin, allocs visible so far)
        self.timeline: list[tuple[float, int]] = []
        self.visible = 0
        self.fanout_s: list[float] = []  # store commit -> hub routed
        self.never_visible = 0  # allocs whose node the hub never reached
        self.duplicate_ids = 0
        self.origin = time.monotonic()
        self._thread = threading.Thread(
            target=self._run, name="bench-observer", daemon=True
        )
        self._thread.start()
        state.subscribe(self._on_write)

    def watch(self, job_id: str, asked: int) -> JobWatch:
        w = JobWatch(job_id, asked)
        with self._lock:
            self._jobs[job_id] = w
        return w

    def start_window(self) -> None:
        with self._lock:
            self.origin = time.monotonic()
            self.timeline = []
            self.visible = 0
            self.fanout_s = []

    # called under the STORE lock: an append and an event, nothing else
    def _on_write(self, index: int, table: str, objs: list, etype: str) -> None:
        if table == self._table and objs:
            self._inbox.append((index, objs, time.monotonic()))
            self._wake.set()

    def _run(self) -> None:
        while not self._stop.is_set():
            self._wake.wait(0.5)
            self._wake.clear()
            while self._inbox:
                self._route(*self._inbox.popleft())

    def _route(self, index: int, objs: list, t_commit: float) -> None:
        by_job: dict[str, list[str]] = {}
        nodes = set()
        for o in objs:
            if o.desired_status != "run":
                continue
            by_job.setdefault(o.job_id, []).append(o.id)
            nodes.add(o.node_id)
        if not nodes:
            return
        missed = 0
        for node_id in nodes:
            # a hang detector, not a latency limit: a node the hub never
            # routes fails its allocs after `hang_s`, nothing sooner does
            if not self._hub.wait_for_node(node_id, index, self._hang_s):
                missed += 1
        now = time.monotonic()
        with self._lock:
            if missed:
                self.never_visible += missed
                return
            self.fanout_s.append(now - t_commit)
            n_new = 0
            for job_id, ids in by_job.items():
                w = self._jobs.get(job_id)
                if w is None:
                    continue
                before = len(w.seen)
                w.seen.update(ids)
                new = len(w.seen) - before
                self.duplicate_ids += len(ids) - new
                n_new += new
                w.commits += 1
                if len(w.seen) >= w.asked and not w.done.is_set():
                    w.t_visible = now
                    w.done.set()
            self.visible += n_new
            self.timeline.append((now - self.origin, self.visible))

    def idle(self) -> bool:
        return not self._inbox

    def stop(self) -> None:
        self._stop.set()
        self._wake.set()
        self._thread.join(timeout=10)
