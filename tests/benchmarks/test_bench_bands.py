"""The Borg cell run in its priority bands (`borg2011-12k-bands`) and the
cell PR 35 adds on it, `prod-backlog`: the fleet is `borg2011-12k`'s for
every seed, the bands and the standing load's arithmetic, that every job
of the window finds room or victims, the traffic's multiset, the shapes
set-up warms read from the program's own two lists, the per-layer
entries and their files, the band rule on clusters made by hand (each
fault found, and the two things it must NOT call a fault: lower work
placed again after the preemption, and lower work on a machine too small
for the ask), the cell rehearsed end to end, and the two accepted
eviction faults planted under the timed path.
"""

import functools
import json
import time
from collections import Counter
from pathlib import Path

import pytest

from bench_helpers_bands import plant
from benchmarks import run as bench_run
from benchmarks.harness import cluster, spec
from benchmarks.reference import density

ROOT = Path(__file__).resolve().parents[2]
BENCH_DIR = ROOT / "benchmarks"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
BORG = json.loads((BENCH_DIR / "configs" / "borg2011-12k.json").read_text())
BANDS = json.loads(
    (BENCH_DIR / "configs" / "borg2011-12k-bands.json").read_text())
MIX = json.loads((BENCH_DIR / "traffic" / "mixed-backlog.json").read_text())
PROD = json.loads((BENCH_DIR / "traffic" / "prod-backlog.json").read_text())
CELL = "borg2011-12k-bands.prod-backlog"
banded_backlog = spec.load_module("generators", "banded_backlog")
bands_rule = spec.load_module(spec.RULES, "preemption_bands")

CPU = sum(c["count"] * c["cpu_mhz"] for c in BANDS["node_classes"])
MEM = sum(c["count"] * c["memory_mb"] for c in BANDS["node_classes"])
BAND_OF = {"production": ("service", 50), "other": ("batch", 30),
           "gratis": ("batch", 10)}


def standing_jobs() -> list[tuple[str, int]]:
    """(job class, jobs of 1,000) of every standing entry, as run.py
    reads `fill_share`: int(share x what the fleet holds) // count."""
    out = []
    for e in BANDS["standing"]:
        ask = BANDS["job_classes"][e["job_class"]]["ask"]
        room = sum(c["count"] * density.allocs_per_node(c, ask)
                   for c in BANDS["node_classes"])
        out.append((e["job_class"], int(e["fill_share"] * room) // e["count"]))
    return out


def held(by) -> tuple[int, int]:
    """(MHz, MB) the standing jobs that `by` admits hold."""
    cpu = mem = 0
    for jc, jobs in standing_jobs():
        c = BANDS["job_classes"][jc]
        if by(jc, c):
            cpu += jobs * 1000 * c["ask"]["cpu_mhz"]
            mem += jobs * 1000 * c["ask"]["memory_mb"]
    return cpu, mem


def window() -> Counter:
    """ask -> allocs of the whole window."""
    out = Counter()
    for ask, count in PROD["period"]:
        out[ask] += count * PROD["periods"]
    return out


# -- the deployment's file -------------------------------------------------

def test_the_fleet_is_borg2011_12ks_letter_for_letter():
    for key in ("nodes", "datacenters", "node_classes"):
        assert BANDS[key] == BORG[key], key
    entry = BENCH["configs"][-1]
    assert entry["name"] == "borg2011-12k-bands"
    assert entry["source"] == BANDS["source"] and len(entry["source"]) <= 200
    assert "12,583 machines" in entry["source"]
    assert sorted(entry["reduced"]) == sorted(BANDS["reduced"]) == sorted(
        BANDS["reduced_why"])
    # the two cuts borg2011-12k lists are taken back; the monitoring band
    # is the one this deployment leaves out
    assert not {"priorities", "job_types", "nodes"} & set(BANDS["reduced"])
    assert "monitoring_band" in BANDS["reduced"]
    assert "priority-lanes" in BANDS["reduced_why"]["monitoring_band"]


@pytest.mark.parametrize("seed", [7, 3_000_000_019])
def test_the_fleet_of_a_seed_is_borg2011_12ks(seed):
    def rows(config):
        fleet = cluster.Fleet(None, config, config["nodes"], seed)
        return [(n.id, n.name, n.datacenter, n.node_class, n.resources.cpu,
                 n.resources.memory_mb, n.resources.disk_mb,
                 sorted(n.attributes.items()), n.computed_class)
                for n in fleet.nodes]
    assert rows(BANDS) == rows(BORG)


def test_the_bands_are_job_classes_over_borg2011_12ks_asks():
    classes = BANDS["job_classes"]
    for name, c in classes.items():
        band, ask = name.split("-", 1)
        assert (c["band"], c["ask_class"]) == (band, ask)
        assert (c["type"], c["priority"]) == BAND_OF[band], name
        theirs = BORG["job_classes"][ask]
        for key in ("ask", "constraints"):
            assert c[key] == theirs[key], (name, key)
        assert c.get("spread") == theirs.get("spread"), name
    # the window's band holds all six asks; each band under it three or more
    by_band = Counter(c["band"] for c in classes.values())
    assert by_band["production"] == 6
    assert by_band["other"] >= 3 and by_band["gratis"] >= 3
    assert BANDS["preemption"]["priority_delta"] == 10
    assert "service" in BANDS["preemption"]["enabled_for"]
    # production rides the batch lane: under the worker's lane priority
    assert BAND_OF["production"][1] < 60
    for key in ("bands", "units", "fill", "free_room", "memory",
                "placeable", "jobs_of", "preemption_enabled_for",
                "cross_node_lowest_first", "stood_all_the_while"):
        assert key in BANDS["assumed"], key


def test_the_standing_load_is_inside_the_issues_ranges():
    jobs = standing_jobs()
    assert all(n >= 1 for _, n in jobs)
    bands = [BANDS["job_classes"][jc]["band"] for jc, _ in jobs]
    # services before batch before gratis, each band in one run
    assert [b for i, b in enumerate(bands) if i == 0 or bands[i - 1] != b] \
        == ["production", "other", "gratis"]
    share = {}
    for band in BAND_OF:
        cpu, mem = held(lambda jc, c: c["band"] == band)
        share[band] = (100 * cpu / CPU, 100 * mem / MEM)
        assert len({jc for jc, _ in jobs
                    if BANDS["job_classes"][jc]["band"] == band}) >= 3
    assert 35 <= share["production"][0] <= 40
    assert 25 <= share["other"][0] <= 30
    assert 20 <= share["gratis"][0] <= 28
    total_cpu = sum(s[0] for s in share.values())
    total_mem = sum(s[1] for s in share.values())
    assert 85 <= total_cpu <= 95
    # the issue asks for memory within five points of the CPU's share:
    # not reachable with these asks on these machines (assumed.memory
    # says why), so the distance is pinned as it stands, not hidden
    assert 15 <= total_cpu - total_mem <= 25
    assert "five points" in BANDS["assumed"]["memory"]
    assert sum(n for _, n in jobs) == 226


@functools.lru_cache(maxsize=None)
def packed(seed: int = 1):
    """The standing load packed as the program packs it — one job at a
    time, best fit first, a spread job a quarter to a datacenter — by
    the program's numpy twin of the compact kernel, at the fleet's full
    size: (cap, used, {band: usage}, platform of each machine)."""
    import numpy as np

    from nomad_tpu.scheduler.tpu.microsolve import (
        solve_placement_compact_micro)

    shapes = BANDS["node_classes"]
    n = BANDS["nodes"]
    deal = cluster.deal_classes(shapes, n, seed)
    cap = np.array([[shapes[k]["cpu_mhz"], shapes[k]["memory_mb"],
                     shapes[k]["disk_mb"]] for k in deal], dtype=np.int64)
    family = np.array(
        [shapes[k]["attributes"]["platform.family"] for k in deal])
    dealt, dc = [0] * len(shapes), np.zeros(n, dtype=np.int64)
    for i, k in enumerate(deal):
        dc[i] = dealt[k] % len(BANDS["datacenters"])
        dealt[k] += 1
    used = np.zeros((n, 3), dtype=np.int64)
    by_band = {band: np.zeros((n, 3), dtype=np.int64) for band in BAND_OF}
    no_bias, no_cap = np.zeros(n, np.float32), np.full(n, 1 << 30, np.int64)
    for jc, jobs in standing_jobs():
        c = BANDS["job_classes"][jc]
        ask = np.array([c["ask"]["cpu_mhz"], c["ask"]["memory_mb"],
                        c["ask"]["disk_mb"]], dtype=np.int64)
        feas = family == "C" if len(c["constraints"]) > 1 \
            else np.ones(n, dtype=bool)
        for _ in range(jobs):
            if c.get("spread"):
                groups = [(ask, 250, feas & (dc == d), no_bias, no_cap)
                          for d in range(4)]
            else:
                groups = [(ask, 1000, feas, no_bias, no_cap)]
            inst, _, after = solve_placement_compact_micro(
                cap, used, groups, 1000)
            assert int((inst >= 0).sum()) == 1000, jc  # the job fits whole
            by_band[c["band"]] += after - used
            used = after
    return cap, used, by_band, family


def test_every_job_of_the_window_finds_room_or_victims():
    """By packing the two files' numbers, as assumed.free_room and
    assumed.placeable say it."""
    import numpy as np

    cap, used, by_band, family = packed()
    asks = {a: BORG["job_classes"][a]["ask"] for a in window()}
    vec = {a: np.array([v["cpu_mhz"], v["memory_mb"], v["disk_mb"]])
           for a, v in asks.items()}
    backlog_cpu = sum(n * asks[a]["cpu_mhz"] for a, n in window().items())

    def holds(room, ask) -> int:
        units = (room // vec[ask]).min(axis=1)
        return int((units * (family == "C")).sum() if ask == "platform-c"
                   else units.sum())

    free = cap - used
    # the free room holds 30-45 % of the backlog by its binding resource
    # (CPU: the window asks 0.77 MHz a MB, the cell has 0.55), counted in
    # the window's commonest ask: what is stranded beside a full
    # dimension is no room
    usable = holds(free, "sand") * asks["sand"]["cpu_mhz"]
    assert 0.30 <= usable / backlog_cpu <= 0.45
    assert f"{holds(free, 'sand'):,} sands" in BANDS["assumed"]["free_room"]
    rest = backlog_cpu - usable
    assert by_band["gratis"][:, 0].sum() >= 1.25 * rest
    # with the gratis band evicted every ask of the window fits, whole,
    # on machines it admits; platform-c on C machines alone, where the
    # lower bands' own platform-c jobs stand by their constraint
    for ask, n in window().items():
        assert holds(free + by_band["gratis"], ask) >= n, ask
    c_cpu, _ = held(lambda jc, c: c["band"] != "production"
                    and c["ask_class"] == "platform-c")
    assert c_cpu >= 2 * window()["platform-c"] * asks["platform-c"]["cpu_mhz"]
    assert sum(c["count"] for c in BANDS["node_classes"]
               if density.allocs_per_node(c, asks["boulder"]) >= 1) == 12_525
    # more than one band to a machine: few before the window (the fill
    # packs one job to a machine; the window's partial evictions make
    # the rest), but the slabs are read on them from the first solve
    bands_on = sum((b[:, 0] > 0).astype(int) for b in by_band.values())
    assert (bands_on >= 2).sum() >= 3


# -- the traffic -------------------------------------------------------------

def test_the_window_is_mixed_backlogs_period_in_the_production_band():
    assert PROD["generator"] == "banded_backlog"
    assert PROD["period"] == MIX["period"] and PROD["submitters"] == 4
    assert sum(n for _, n in PROD["period"]) == 3292
    assert PROD["band"] == "production" and 16 <= PROD["periods"] <= 32
    jobs = banded_backlog.deal(11, PROD)
    assert len(jobs) == 40 * PROD["periods"]
    assert {jc for jc, _ in jobs} == {
        n for n, c in BANDS["job_classes"].items()
        if c["band"] == "production"}
    for key in ("period", "periods"):
        assert key in PROD["assumed"], key
    cell = BENCH["workloads"][-1]
    assert str(len(jobs)) in cell["why"].replace(",", "")
    assert f"{3292 * PROD['periods']:,}" in cell["why"]


@pytest.mark.parametrize("params", [PROD, {**PROD, **PROD["rehearsal"]}],
                         ids=["cell", "rehearsal"])
def test_every_seed_deals_the_same_multiset_in_another_order(params):
    a, b = (banded_backlog.deal(s, params) for s in (5, 3_000_000_019))
    assert Counter(a) == Counter(b) and a != b
    per = len(params["period"])
    for k in range(int(params["periods"])):
        assert Counter(a[k * per:(k + 1) * per]) == Counter(
            (f"production-{ask}", n) for ask, n in params["period"])


def test_shapes_lists_one_dry_batch_for_every_program_in_reach():
    from nomad_tpu.scheduler.tpu.kernels import (
        compact_programs, pad_c, pad_g, preempt_programs)

    shapes = banded_backlog.shapes(PROD, BANDS)
    classes = BANDS["job_classes"]
    dcs = len(BANDS["datacenters"])

    def lands(shape):
        c = classes[shape["job_class"]]
        groups = shape["evals"] * (dcs if c.get("spread") else 1)
        count = shape["count"] // (dcs if c.get("spread") else 1)
        assert shape["evals"] <= 64
        assert shape["evals"] * shape["count"] > 48  # past the host stack
        return c["band"], pad_g(groups), pad_c(count)

    got = [lands(s) for s in shapes]
    preempt = [gp for band, gp, _ in got if band == "production"]
    assert preempt == [gp for gp, _ in preempt_programs()] == [8, 32, 128, 256]
    assert {tp for _, tp in preempt_programs()} == {4}  # three bands
    compact = [(gp, maxc) for band, gp, maxc in got if band == "gratis"]
    # a follow-up eval asks at most a standing job's 1,000 allocs
    assert compact == [p for p in compact_programs() if p[1] <= 1024]
    assert len(got) == len(preempt) + len(compact)


def test_warm_jobs_are_one_real_deploy_of_each_production_ask():
    warm = banded_backlog.warm_jobs(PROD)
    assert [jc for _, jc, _ in warm] == [
        f"production-{ask}" for ask, _ in PROD["warm"]]
    assert {jc for _, jc, _ in warm} == {
        n for n, c in BANDS["job_classes"].items()
        if c["band"] == "production"}
    assert all(priority is None for _, _, priority in warm)
    assert PROD["priority"] == 50  # what run.py sends a warm deploy with
    # a rehearsal fills its tiny fleet first, each band at its priority
    tiny = banded_backlog.warm_jobs({**PROD, **PROD["rehearsal"]})
    fill = [w for w in tiny if not w[1].startswith("production-")
            or w[2] is not None]
    assert fill == tiny[:len(fill)] and len(fill) >= 5
    for _, jc, priority in fill:
        assert priority == BANDS["job_classes"][jc]["priority"]


# -- BENCHMARK.json ----------------------------------------------------------

NAMES = ["kernel_ms_per_batch", "solve_placement_preempt_banded_roofline",
         "device_idle_share", "compiles_in_window", "programs_new",
         "batch_evals_mean", "chain_wait_ms_per_batch", "plans_trimmed",
         "lower_ms_per_batch", "prefix_ms_per_batch", "readback_ms_per_batch",
         "victims_ms_per_batch", "materialize_ms_per_batch",
         "evictions_per_placement", "evicting_share", "plan_submit_p50_ms",
         "raft_apply_p50_ms", "register_p50_ms", "broker_wait_p50_ms",
         "watch_fanout_p95_ms", "kernel_path_share", "host_stack_path_share",
         "higher_band_victims_share", "multi_tier_nodes_mean"]


def test_the_cell_and_its_metrics_are_appended():
    assert BENCH["workloads"][-1] == {
        "name": CELL, "config": "borg2011-12k-bands",
        "traffic": "prod-backlog", "chips": 1,
        "why": BENCH["workloads"][-1]["why"]}
    assert [w["name"] for w in BENCH["workloads"]].count(CELL) == 1
    assert sum(w["config"] == "borg2011-12k-bands"
               for w in BENCH["workloads"]) == 1
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["placements_per_s"]["workloads"][-1] == CELL
    assert CELL not in e2e["packing_share"]["workloads"]
    assert CELL not in e2e["e2e_p50_ms"]["workloads"]
    mine = [m for m in BENCH["per_layer"] if CELL in m.get("workloads", ())]
    assert BENCH["per_layer"][-len(mine):] == mine
    assert [m["name"] for m in mine] == [
        n if n.endswith("_roofline") else f"{n}.prod-backlog" for n in NAMES]
    reducers = {p.stem for p in (BENCH_DIR / "reducers").glob("*.py")}
    for m in mine:
        assert m["workloads"] == [CELL] and m["moves"] == "placements_per_s"
        f = json.loads((BENCH_DIR / "layer_metrics"
                        / f"{m['name']}.json").read_text())
        assert f["traffic"] == ["prod-backlog"] and f["reducer"] in reducers
        twin = BENCH_DIR / "layer_metrics" / m["name"].replace(
            ".prod-backlog", ".mixed-backlog.json")
        if twin.exists() and m["name"].split(".")[0] not in (
                "programs_new", "kernel_ms_per_batch"):  # their own kernel's
            theirs = json.loads(twin.read_text())  # the shared path
            assert all(f[k] == v for k, v in theirs.items()
                       if k not in ("name", "traffic")), m["name"]
    roof = json.loads((BENCH_DIR / "layer_metrics" / (
        "solve_placement_preempt_banded_roofline.json")).read_text())
    theirs = json.loads((BENCH_DIR / "layer_metrics" / (
        "solve_placement_preempt_roofline.json")).read_text())
    assert all(roof[k] == v for k, v in theirs.items()
               if k not in ("name", "traffic"))
    # the files this PR may not touch are as the parent has them
    assert [g["rule"] for g in BANDS["guarantees"]] == [
        "standing_held_or_evicted", "preemption_bands", "unique_allocs",
        "node_capacity", "job_feasibility", "asks_carried",
        "watch_visibility"]
    assert BANDS["may_remain"] == ["blocked_evals"]


# -- the band rule on clusters made by hand ----------------------------------

def node(i, cpu=16000, mem=32768, family="B", dc="dc1"):
    return {"id": f"n{i}", "datacenter": dc, "class": "", "cpu": cpu,
            "mem": mem, "disk": 204800,
            "attributes": {"kernel.name": "linux",
                           "platform.family": family}, "devices": []}


SIZES = {"sand": (400, 512), "small": (800, 1024), "boulder": (8000, 16384),
         "platform-c": (4000, 4096)}


def alloc(aid, job_id, node_id, ask="sand", name=None):
    cpu, mem = SIZES[ask]
    return {"id": aid, "name": name or aid, "job": job_id, "node": node_id,
            "cpu": cpu, "mem": mem, "disk": 300}


def victim(aid, job_id, node_id, by, name=None):
    return {"id": aid, "name": name or aid, "job": job_id, "node": node_id,
            "desired_status": "evict", "client_status": "running",
            "preempted_by_allocation": by}


def job(priority, family=None):
    constraints = [("${attr.kernel.name}", "=", "linux")]
    if family:
        constraints.append(("${attr.platform.family}", "=", family))
    return {"datacenters": ["dc1"], "priority": priority, "type": "service",
            "constraints": constraints}


def ask_of(name):
    cpu, mem = SIZES[name]
    return {"cpu_mhz": cpu, "memory_mb": mem, "disk_mb": 300}


def sound() -> tuple[dict, dict]:
    """n0 was full of 40 gratis sands, n1 of 20 `other` smalls, n2 (a C
    machine) of 8 `other` platform-c. Production placed: one small on n0
    for two gratis sands, one boulder on n0 for twenty more, and — no
    gratis stands on a C machine — one platform-c on n2 for one `other`
    platform-c. A gratis sand evicted from n0 was placed again on n1
    (into room another run of the story freed): lower work beside an
    `other` victim's node that did NOT stand all the while."""
    live = [alloc(f"g{i}", "gratis", "n0") for i in range(22, 40)]
    live += [alloc(f"o{i}", "other", "n1", "small") for i in range(20)]
    live += [alloc(f"c{i}", "other-c", "n2", "platform-c")
             for i in range(1, 8)]
    live += [alloc("p-small", "prod-small", "n0", "small"),
             alloc("p-boulder", "prod-boulder", "n0", "boulder"),
             alloc("p-c", "prod-c", "n2", "platform-c"),
             alloc("g0-again", "gratis", "n2", name="g0")]
    dead = [victim("g0", "gratis", "n0", "p-small"),
            victim("g1", "gratis", "n0", "p-small")]
    dead += [victim(f"g{i}", "gratis", "n0", "p-boulder")
             for i in range(2, 22)]
    dead += [victim("c0", "other-c", "n2", "p-c")]
    snap = {"nodes": [node(0), node(1), node(2, 32000, 65536, "C")],
            "jobs": {"gratis": job(10), "other": job(30),
                     "other-c": job(30, "C"), "prod-small": job(50),
                     "prod-boulder": job(50), "prod-c": job(50, "C")},
            "allocs": live, "terminal_allocs": dead, "observed": {}}
    expected = {"gratis": (40, ask_of("sand")),
                "other": (20, ask_of("small")),
                "other-c": (8, ask_of("platform-c")),
                "prod-small": (1, ask_of("small")),
                "prod-boulder": (1, ask_of("boulder")),
                "prod-c": (1, ask_of("platform-c"))}
    return snap, expected


def too_close(s):  # (a)
    s["jobs"]["prod-small"] = job(15)


def lower_band_stood_on_the_node(s):  # (b): p-boulder took an `other`
    # small that stood on n0, and two gratis sands stayed in its place
    s["allocs"] = [a for a in s["allocs"] if a["id"] != "o0"]
    s["terminal_allocs"] = [t for t in s["terminal_allocs"]
                            if t["id"] not in ("g20", "g21")]
    s["terminal_allocs"].append(victim("o0", "other", "n0", "p-boulder"))
    s["allocs"] += [alloc("g20", "gratis", "n0"),
                    alloc("g21", "gratis", "n0")]


def lower_band_stood_elsewhere(s):  # (c): p-c took `other` while gratis
    # platform-c work stood, all the while, on another C machine
    s["nodes"].append(node(3, 32000, 65536, "C"))
    s["jobs"]["gratis-c"] = job(10, "C")
    s["allocs"].append(alloc("gc0", "gratis-c", "n3", "platform-c"))


def one_victim_too_many(s):  # (d): p-small took a third sand
    s["allocs"] = [a for a in s["allocs"] if a["id"] != "g22"]
    s["terminal_allocs"].append(victim("g22", "gratis", "n0", "p-small"))


def orphan(s):  # (e)
    s["terminal_allocs"].append(victim("g23", "gratis", "n0", "gone"))
    s["allocs"] = [a for a in s["allocs"] if a["id"] != "g23"]


@pytest.mark.parametrize("alter, says", [
    (None, None),
    (too_close, "not 10 priorities under"),
    (lower_band_stood_on_the_node, "on the same node"),
    (lower_band_stood_elsewhere, "above the lowest band"),
    (one_victim_too_many, "made unnecessary"),
    (orphan, "preemptor is not live"),
])
def test_the_band_rule_finds_each_fault_and_only_it(alter, says):
    snap, expected = sound()
    if alter is not None:
        alter(snap)
    faults = bands_rule.check(snap, expected, BANDS)
    if says is None:
        assert faults == []
    else:
        assert len(faults) == 1 and says in faults[0], faults


def test_lower_work_that_proves_nothing_is_no_fault():
    """What `preemption_lowest_first`'s rule (c) would call a fault and
    is none here: the gratis sand placed AGAIN beside the `other`
    platform-c victim (it did not stand all the while), and gratis work
    that did, on a machine the preemptor admits, too small to hold its
    ask whatever is evicted."""
    snap, expected = sound()
    lowest_first = spec.load_module(spec.RULES, "preemption_lowest_first")
    assert any("above the lowest tier" in f
               for f in lowest_first.check(snap, expected, BANDS))
    assert bands_rule.check(snap, expected, BANDS) == []
    # a whole gratis platform-c that stood all the while on a C machine
    # is a fault (above); three gratis sands there are not: 1,200 MHz of
    # victims do not make a platform-c's 4,000
    snap["nodes"].append(node(3, 32000, 65536, "C"))
    snap["allocs"] += [alloc(f"s{i}", "gratis", "n3") for i in range(40, 43)]
    expected["gratis"] = (43, ask_of("sand"))
    assert bands_rule.check(snap, expected, BANDS) == []
    # ten of them are: 4,000 MHz and 5,120 MB hold the whole ask
    snap["allocs"] += [alloc(f"s{i}", "gratis", "n3") for i in range(43, 50)]
    assert any("above the lowest band" in f
               for f in bands_rule.check(snap, expected, BANDS))


# -- the cell, rehearsed -----------------------------------------------------

def checks_of(line: dict) -> dict:
    return {k: v["value"] for k, v in line["checks"].items()}


def rehearse(capsys, trace: int, seed: int, seconds: float = 5.0):
    rc = bench_run.main(
        ["--workload", CELL, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace), "--rehearsal"], time.monotonic())
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    report = json.loads(
        (BENCH_DIR / "out" / f"{CELL}.{seed}.json").read_text())
    return json.loads(out[-1]), report


def test_the_rehearsed_window_places_by_evicting_on_warm_programs(capsys):
    line, report = rehearse(capsys, 1, 3_000_000_035)
    assert line["correct"] is True, report["store_faults"]
    assert set(checks_of(line)) == {
        "faults.standing_held_or_evicted", "faults.preemption_bands",
        "faults.unique_allocs", "faults.node_capacity",
        "faults.job_feasibility", "faults.asks_carried",
        "faults.watch_visibility", "failed", "compiles_in_window",
        "left_in_flight"}
    assert all(v == 0 for v in checks_of(line).values())
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # no time figure and no device figure from XLA:CPU
    assert set(m) == {f"{n}.prod-backlog" for n in (
        "compiles_in_window", "programs_new", "batch_evals_mean",
        "plans_trimmed", "evictions_per_placement", "evicting_share",
        "kernel_path_share", "host_stack_path_share",
        "higher_band_victims_share", "multi_tier_nodes_mean")}
    assert m["programs_new.prod-backlog"] == 0
    assert m["plans_trimmed.prod-backlog"] == 0
    # the route: solves that may preempt on the tier kernel; band
    # `other` touched only where no gratis victim makes room (the rule
    # above says each such victim was sound); tiers that share nodes
    assert m["kernel_path_share.prod-backlog"] > 0
    assert 0 <= m["higher_band_victims_share.prod-backlog"] < 20
    assert m["multi_tier_nodes_mean.prod-backlog"] > 0
    assert 20 <= m["evicting_share.prod-backlog"] <= 100
    assert m["evictions_per_placement.prod-backlog"] >= 1
    # every alloc of the window, and the few the evicted jobs' follow-up
    # evals placed again inside it: the observer counts a standing job's
    # new allocs too (PERF.md section 7)
    asked = sum(n for _, n in PROD["rehearsal"]["period"])
    assert asked <= report["attempted"] <= 1.05 * asked
    assert report["watched_counters"]["nomad.tpu.chain_parent_failed"] == 0
    # one dry solve for each program of the two lists in reach
    assert report["setup"]["dry_solves"] == len(
        banded_backlog.shapes(PROD, BANDS))


@pytest.mark.parametrize("fault", ["evict_higher_tier",
                                   "evict_without_need_shown"])
def test_a_planted_eviction_fault_is_found_by_the_band_rule_alone(
        capsys, fault):
    # a planted victim stands only if no later round of its batch takes
    # it for a preemptor of its own (bench_helpers_bands.plant): on the
    # rehearsal's few nodes one run in five leaves none, so a seed more
    for seed in (3_000_000_036, 3_000_000_037, 3_000_000_038):
        undo, planted = plant(fault)
        try:
            line, report = rehearse(capsys, 0, seed)
        finally:
            undo()
        if planted():
            break
    assert planted()
    assert line["correct"] is False
    got = checks_of(line)
    assert got["faults.preemption_bands"] >= 1, report["store_faults"]
    assert {k: v for k, v in got.items()
            if k.startswith("faults.") and v} == {
        "faults.preemption_bands": got["faults.preemption_bands"]}
    assert got["compiles_in_window"] == 0 and got["left_in_flight"] == 0
