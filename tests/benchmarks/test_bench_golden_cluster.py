"""`c1m-5k` and `c2m-10k` give, for every seed, the cluster and the job
bodies they gave before a deployment became data (PR 26): node ids in
the same order, the datacenter and size of each, the same random draws
left for the heartbeat phases, and `jobs.encode(make_job(...))` byte for
byte. The digests were taken from the parent's code (commit 317c703)
before `Fleet` and `make_job` learnt of classes."""

import hashlib
import json
from pathlib import Path

import pytest

from benchmarks.harness import cluster, jobs, spec

BENCH_DIR = Path(__file__).resolve().parents[2] / "benchmarks"

FLEETS = {  # (config, seed): (digest of the nodes, digest of the rng after)
    ("c1m-5k", 7): (
        "6b97c38821672d5c5185b9106af756c1c98ccb6d170d2d1b82b6ccde7fba8f85",
        "d5a434e86e13419ee34150d544be88d50671050a0ecc05004b265013cc4108d2"),
    ("c1m-5k", 3000000019): (
        "ebd61d6f3ec489c7cdeac530844537ccdf04c702a06fcf0c7bf044997d9d8a27",
        "665fb7427613541de3358163a547b8bbf0f8a23a0724aa43f96447e327795aa2"),
    ("c2m-10k", 7): (
        "71b5b62322af534a84ad6e5114b9eaeab676755d24294d4625656e2a203c6696",
        "3924fb6e488be3167334b605a857edfbcac64907726f4bb8e50593304776f134"),
    ("c2m-10k", 3000000019): (
        "5ffa4a4e4799e15cb859610e599fec8fa200a8510c35068bb9886e29c7dd6471",
        "fbca1c4405b9b8f302c4d6a4490aa01985e938ef81dcf7fd2996793c0e4c0229"),
}
BODIES = {  # (config, count, priority): digest of the encoded job
    ("c1m-5k", 1000, 50):
        "eb42bc6fdc72d1f6ca396fc617779f10785028553196a22c0a001df9f79a467f",
    ("c1m-5k", 6, 70):
        "289f667433ca5d5a7f42511e675ec84399cb70c8e60a9b9eac4fe8d3585cc210",
    ("c2m-10k", 1000, 50):
        "cc57375ea4bba674bc8210fd530ece22607b3479b684ee09fc1bcb2922556c67",
    ("c2m-10k", 6, 70):
        "0669a7c861d579f743e8d50676a867b3684f3c74f434caa9a6a1a1b1a349d08f",
}


def config_of(name: str) -> dict:
    return json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name, seed", sorted(FLEETS))
def test_the_fleet_of_a_seed_is_the_parents(name, seed):
    config = config_of(name)
    fleet = cluster.Fleet(None, config, config["nodes"], seed)
    rows = [(n.id, n.name, n.datacenter, n.resources.cpu,
             n.resources.memory_mb, n.resources.disk_mb,
             sorted(n.attributes.items())) for n in fleet.nodes]
    assert hashlib.sha256(json.dumps(rows).encode()).hexdigest() == \
        FLEETS[name, seed][0]
    assert hashlib.sha256(repr(fleet.rng.getstate()).encode()).hexdigest() \
        == FLEETS[name, seed][1]
    # one class: every node offers the same, and the program's digest of
    # what a node offers tells only the datacenters apart
    assert len({n.computed_class for n in fleet.nodes}) == \
        len(config["datacenters"])


@pytest.mark.parametrize("name, count, priority", sorted(BODIES))
def test_a_job_body_is_the_parents_byte_for_byte(name, count, priority):
    job = jobs.make_job(config_of(name), f"golden-{name}", count, priority)
    job.submit_time = 0  # the one field that reads the clock
    assert hashlib.sha256(jobs.encode(job)).hexdigest() == \
        BODIES[name, count, priority]


def test_the_short_forms_mean_one_class():
    config = config_of("c2m-10k")
    assert spec.node_classes(config) == [config["node"]]
    assert spec.job_classes(config) == {"default": {
        "ask": config["ask"], "constraints": config["constraints"],
        "spread": config["spread"]}}
    assert spec.job_class(config) is not None
    with pytest.raises(spec.SpecError, match="no job class"):
        spec.job_class(config, "batch")


@pytest.mark.parametrize("seed", [1, 3000000019])
def test_classes_are_dealt_by_share_from_the_seed(seed):
    classes = [{"name": "small", "share": 0.75}, {"name": "large", "share": 0.25}]
    deal = cluster.deal_classes(classes, 256, seed)
    assert deal.count(0) == 192 and deal.count(1) == 64
    assert deal == cluster.deal_classes(classes, 256, seed)
    assert deal != cluster.deal_classes(classes, 256, seed + 1)
    assert deal != sorted(deal)  # dealt over the fleet, not in blocks
    # counts are weights too, and a remainder goes to the nearest class
    thirds = cluster.deal_classes(
        [{"count": 1}, {"count": 1}, {"count": 1}], 10, seed)
    assert sorted(thirds.count(k) for k in range(3)) == [3, 3, 4]
    assert cluster.deal_classes([{"share": 1}], 5, seed) == [0] * 5
