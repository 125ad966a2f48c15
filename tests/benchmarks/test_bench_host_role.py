"""The per-layer metrics that read the program's account of its
threads' CPU (PR 37): the `host_role` reducer's forms on hand-made
samples, and the sixteen files — each loads, names a reducer that
exists, lists the cells its traffic runs in, and none lists a cell whose
own test pins its metrics to the names its PR wrote."""

import json
from pathlib import Path

import pytest

from benchmarks.harness import spec

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = {"bulk": ["c2m-10k.bulk", "c1m-5k.bulk"],
         "deploys": ["c1m-5k.deploys", "c2m-10k.deploys"]}
MOVES = {"bulk": "placements_per_s", "deploys": "e2e_p50_ms"}
PINNED_CELLS = {"c2m-10k-tiers.preempt-fill", "borg2011-12k.mixed-backlog",
                "borg2011-12k-bands.prod-backlog"}
HARNESS = ["bench-fleet", "bench-submit", "bench-observer", "bench-operator"]
# short name -> (roles, layer), in the order of ISSUE 37's table
ROLES = {
    "solve": (["solve"], "host_prep"),
    "commit": (["commit"], "readback + materialize"),
    "apply": (["applier", "raft"], "plan.submit"),
    "http": (["http"], "front door"),
    "watcher": (["deployment-watcher"], "deployment watcher"),
    "journal": (["blackbox-pump"], "journal"),
}
SHARES = {"harness_cpu_share": (HARNESS, "harness"),
          "unaccounted_cpu_share": (["(unaccounted)"], "device")}
ADDED = [name for short in ROLES for name in (
    f"{short}_cpu_s.bulk", f"{short}_cpu_ms_per_deploy.deploys")] + [
    f"{name}.{traffic}" for name in SHARES for traffic in CELLS]

HOST_ROLE = spec.load_module("reducers", "host_role")
BY_ROLE = {"solve": 1.5, "applier": 1.0, "raft": 3.0, "bench-fleet": 2.5,
           "(unaccounted)": 2.0}


def samples(by_role=BY_ROLE, deploys=4):
    return {"host": {"busy_s_by_thread_role": by_role},
            "client": {"e2e_s": [0.01] * deploys}}


def metric_file(name: str) -> dict:
    return json.loads(
        (ROOT / "benchmarks" / "layer_metrics" / f"{name}.json").read_text())


@pytest.mark.parametrize("spec_, want", [
    ({"roles": ["solve"]}, 1.5),
    ({"roles": ["applier", "raft"]}, 4.0),
    ({"roles": ["http"]}, 0.0),  # no thread of the role: it used no CPU
    ({"roles": ["applier", "raft"], "per": {"client": "e2e_s"},
      "scale": 1000.0}, 1000.0),
    ({"roles": HARNESS, "over": "all"}, 25.0),
    ({"roles": ["(unaccounted)"], "over": "all"}, 20.0),
], ids=["sum", "sum_of_two", "absent_role", "per", "share", "residual"])
def test_the_reducers_forms(spec_, want):
    assert HOST_ROLE.reduce(samples(), spec_, {}) == pytest.approx(want)


@pytest.mark.parametrize("given, spec_", [
    ({"host": {}, "client": {"e2e_s": [0.01]}}, {"roles": ["solve"]}),
    ({"client": {"e2e_s": [0.01]}}, {"roles": ["solve"], "over": "all"}),
    (samples(deploys=0), {"roles": ["solve"], "per": {"client": "e2e_s"}}),
    (samples(by_role={}), {"roles": ["solve"], "over": "all"}),
], ids=["untraced", "no_block", "no_deploy", "nothing_to_share"])
def test_nothing_to_read_is_none_and_never_raises(given, spec_):
    assert HOST_ROLE.reduce(given, spec_, {}) is None


def test_a_parents_block_without_the_residual_reads_and_does_not_raise():
    parent = {"host": {"busy_s_by_thread_role": {"solve": 9.69}}}
    assert HOST_ROLE.reduce(
        parent, metric_file("unaccounted_cpu_share.bulk"), {}) == 0.0


@pytest.mark.parametrize("name", ADDED)
def test_each_file_loads_and_lists_the_cells_its_traffic_runs_in(name):
    f = metric_file(name)
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    traffic = name.rsplit(".", 1)[1]
    assert f["traffic"] == [traffic] and f["reducer"] == "host_role"
    assert (ROOT / "benchmarks" / "reducers" / "host_role.py").is_file()
    assert entry["workloads"] == CELLS[traffic]
    assert not PINNED_CELLS & set(entry["workloads"])
    traffic_of = {w["name"]: w["traffic"] for w in BENCH["workloads"]}
    assert {traffic_of[c] for c in entry["workloads"]} == {traffic}
    assert entry["moves"] == f["moves"] == MOVES[traffic]
    assert (entry["source"], entry["better"]) == ("program_counter", "lower")
    short = name.split("_cpu_")[0]
    roles, layer = ROLES.get(short) or SHARES[name.rsplit(".", 1)[0]]
    assert f["roles"] == roles and entry["layer"] == f["layer"] == layer
    if short in ROLES and traffic == "deploys":
        assert (f["per"], f["scale"], f["unit"]) == (
            {"client": "e2e_s"}, 1000.0, "ms")
    elif short in ROLES:
        assert "per" not in f and "over" not in f and f["unit"] == "s"
    else:
        assert f["over"] == "all" and f["unit"] == "%"
    got = HOST_ROLE.reduce(samples(), f, {})
    assert got is not None and got >= 0.0


def test_the_sixteen_are_appended_in_the_tables_order_after_pr_35s():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-16:] == ADDED
    assert names[-17] == "multi_tier_nodes_mean.prod-backlog"
    # every role a file names is one the program gives a thread
    from nomad_tpu import hostobs

    threads = {"solve": "tpu-batch-solve", "commit": "tpu-batch-commit",
               "applier": "plan-applier", "raft": "raft-apply-server",
               "http": "Thread-7 (process_request_thread)",
               "deployment-watcher": "deployment-watcher",
               "blackbox-pump": "blackbox-pump",
               "bench-fleet": "bench-fleet-3", "bench-submit": "bench-submit-0",
               "bench-observer": "bench-observer",
               "bench-operator": "bench-operator-1"}
    named = {r for n in ADDED for r in metric_file(n)["roles"]}
    assert named - {hostobs.UNACCOUNTED} == set(threads)
    for role, thread_name in threads.items():
        assert hostobs._role_of(thread_name) == role
