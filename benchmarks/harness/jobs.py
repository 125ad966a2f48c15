"""Jobs as the configuration asks for them, and the client's side of the
front door.

A job is the mock service job with the ask, constraints, datacenters and
spread of one of the configuration's job classes — `bench.py` `add_jobs`
and `chip_smoke.py` `c2m_job`, with the numbers taken from the
configuration's file. The front door is `PUT /v1/jobs` over HTTP on a
connection of its own, as `nomad job run` makes one.
"""

from __future__ import annotations

import http.client
import json
import time

from benchmarks.harness import spec


def make_job(config: dict, job_id: str, count: int,
             priority: int | None = None, job_class: str | None = None):
    """A one-group job of `count` allocs at the ask of `job_class` (None:
    the configuration's first, for one `ask` its only class). `priority`
    None is the class's own, or 50."""
    from nomad_tpu import mock
    from nomad_tpu.structs import Constraint, Spread
    from nomad_tpu.structs.structs import RequestedDevice

    cls = spec.job_class(config, job_class)
    job = mock.job(id=job_id)
    job.datacenters = list(cls.get("datacenters") or config["datacenters"])
    job.priority = int(cls.get("priority", 50) if priority is None
                       else priority)
    if cls.get("type"):
        job.type = cls["type"]
    tg = job.task_groups[0]
    tg.count = count
    res = tg.tasks[0].resources
    res.cpu = cls["ask"]["cpu_mhz"]
    res.memory_mb = cls["ask"]["memory_mb"]
    res.networks = []
    res.devices = [RequestedDevice(name=d["name"], count=int(d["count"]))
                   for d in cls.get("devices", ())]
    job.constraints = [
        Constraint("${attr." + c["attribute"] + "}", c["value"], c["operand"])
        for c in cls.get("constraints") or ()
    ]
    if cls.get("spread"):
        job.spreads = [Spread(
            attribute="${node." + cls["spread"]["attribute"] + "}",
            weight=cls["spread"]["weight"],
        )]
    return job


def encode(job) -> bytes:
    from nomad_tpu import codec

    return json.dumps(
        {"Job": codec.to_wire(job)}, default=codec.json_default
    ).encode()


def put_job(addr: tuple[str, int], body: bytes,
            timeout_s: float = 120.0) -> tuple[int, object, float, float]:
    """PUT /v1/jobs. Returns (status, answer, t_sent, t_answered) on the
    monotonic clock; a transport error reads as status 0."""
    conn = http.client.HTTPConnection(*addr, timeout=timeout_s)
    t_sent = time.monotonic()
    try:
        conn.request("PUT", "/v1/jobs", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
        t_done = time.monotonic()
        status = resp.status
    except (OSError, http.client.HTTPException) as e:
        return 0, {"error": repr(e)}, t_sent, time.monotonic()
    finally:
        conn.close()
    try:
        answer = json.loads(raw or b"{}")
    except ValueError:
        answer = {"raw": raw[:200].decode("latin-1")}
    return status, answer, t_sent, t_done
