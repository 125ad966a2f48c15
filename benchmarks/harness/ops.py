"""What an operation is, when it has failed, and the window's clock.

A generator drives the window through a `RunContext`: it makes an `Op`
for every job, sends it, and (in a closed loop) waits for it. The rules
that turn what happened into `attempted` and `failed` live here and
nowhere else:

An operation FAILS only by a definitive wrong outcome —
  front_door      a non-2xx answer (or no answer) to `PUT /v1/jobs`
  eval_failed     its evaluation ended `failed`
  not_visible     it was not visible on its nodes' watches when the
                  drain's deadline passed (a hang detector: 60 s after
                  the window, not a latency limit)
  store           an invariant of the store is broken (reference/):
                  an alloc placed twice, on a node its job does not
                  admit, over a node's capacity, or an acked job the
                  store does not hold
— never by timing. Completion is eventual: a plan the applier trimmed
and a follow-up eval completed is a slow success and its whole time
counts; a deploy in flight when the window closes is waited for.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field

from benchmarks.harness import jobs, spec
from benchmarks.harness.observer import JobWatch, Observer

DRAIN_DEADLINE_S = 60.0


@dataclass
class Op:
    job_id: str
    asked: int
    kind: str
    watch: JobWatch
    ask: dict  # what every alloc of the job asks, as it was sent
    sent: bool = False
    status: int = 0
    answer: object = None
    t_sent: float = 0.0
    t_acked: float = 0.0
    failed: str = ""  # the cause, once it has one

    @property
    def acked(self) -> bool:
        return self.sent and self.status // 100 == 2

    @property
    def visible(self) -> int:
        return min(len(self.watch.seen), self.asked)


class RunContext:
    def __init__(self, *, config: dict, params: dict, seed: int,
                 seconds: float, http: tuple[str, int],
                 observer: Observer) -> None:
        self.config = config
        self.params = params
        self.seed = seed
        self.seconds = seconds
        self.http = http
        self.observer = observer
        self.ops: list[Op] = []
        self._lock = threading.Lock()
        self.t_open = 0.0
        self.t_end = float("inf")
        self.on_open = lambda: None

    def new_op(self, job_id: str, asked: int, kind: str,
               job_class: str | None = None) -> Op:
        op = Op(job_id, asked, kind, self.observer.watch(job_id, asked),
                spec.job_class(self.config, job_class)["ask"])
        with self._lock:
            self.ops.append(op)
        return op

    def open_window(self) -> None:
        """The first measured operation follows at once: set-up ends
        here."""
        self.on_open()
        self.observer.start_window()
        self.t_open = self.observer.origin
        self.t_end = self.t_open + self.seconds

    def send(self, op: Op, body: bytes) -> None:
        op.sent = True
        op.status, op.answer, op.t_sent, op.t_acked = jobs.put_job(
            self.http, body
        )
        op.watch.t_sent, op.watch.t_acked = op.t_sent, op.t_acked
        # the answer to a 2xx PUT is the eval's id (api/client.py)
        op.watch.eval_id = op.answer if isinstance(op.answer, str) else ""
        if not op.acked:
            op.failed = "front_door"

    def await_visible(self, op: Op) -> None:
        now = time.monotonic()
        open_for = max(self.t_end - now, 0.0) if self.t_open else 0.0
        op.watch.done.wait(open_for + DRAIN_DEADLINE_S)


def settle(cluster, ctx: RunContext, deadline_s: float = DRAIN_DEADLINE_S,
           may_remain: tuple = ()):
    """After the window: nothing more is sent; wait until every acked
    operation is visible and the broker, the plan queue and the blocked
    evals are empty — stopping a server while follow-up evals commit
    raises raft-apply timeouts (PERF.md, PR 21). `may_remain` lists what
    the configuration says stays for good (evals blocked on a cluster
    filled to capacity); nothing else is ignored. Returns what was still
    in flight at the deadline, empty when the system settled."""
    end = time.monotonic() + deadline_s
    left: dict = {}
    while True:
        pending = [op for op in ctx.ops
                   if op.acked and not op.watch.done.is_set()]
        left = {k: v for k, v in cluster.in_flight().items()
                if v and k not in may_remain}
        if pending:
            left["ops_not_visible"] = len(pending)
        if not ctx.observer.idle():
            left["observer"] = 1
        if not left or time.monotonic() >= end:
            return left
        time.sleep(0.05)


def judge(ctx: RunContext, state) -> dict[str, int]:
    """Failures by cause over the operations, after `settle`. An op that
    already has a cause keeps it."""
    causes = {"front_door": 0, "eval_failed": 0, "not_visible": 0}
    for op in ctx.ops:
        if not op.sent:
            continue
        if not op.failed and not op.watch.done.is_set():
            ev = state.eval_by_id(op.watch.eval_id) if op.watch.eval_id \
                else None
            op.failed = ("eval_failed"
                         if ev is not None and ev.status == "failed"
                         else "not_visible")
        if op.failed:
            causes[op.failed] = causes.get(op.failed, 0) + 1
    return causes
