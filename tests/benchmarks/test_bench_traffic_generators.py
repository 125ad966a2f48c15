"""The deploys generator's sizes are a pure function of the seed, and
every seed deals the same multiset in another order."""

import itertools
import json
from collections import Counter
from pathlib import Path

from benchmarks.harness import spec

BENCH_DIR = Path(__file__).resolve().parents[2] / "benchmarks"
PARAMS = json.loads((BENCH_DIR / "traffic" / "deploys.json").read_text())
closed_loop = spec.load_module("generators", "closed_loop")
backlog = spec.load_module("generators", "backlog")


def first(seed, n):
    return list(itertools.islice(closed_loop.sizes(seed, PARAMS), n))


def test_sizes_are_a_pure_function_of_the_seed():
    assert first(2_999_999_999, 500) == first(2_999_999_999, 500)
    assert first(1, 500) != first(2, 500)


def test_every_seed_deals_the_same_multiset_a_super_period():
    period = PARAMS["period"]
    super_period = period * len(PARAMS["small_counts"]) * len(
        PARAMS["rollout_counts"])
    want = Counter(first(0, super_period))
    assert sum(n for (_, roll), n in want.items() if roll) * period == \
        super_period  # one deploy in eight is a rollout
    smalls = {c: n for (c, roll), n in want.items() if not roll}
    assert set(smalls) == set(PARAMS["small_counts"])
    assert len(set(smalls.values())) == 1  # drawn evenly
    for seed in (1, 17, 3_000_000_019):
        two = first(seed, 2 * super_period)
        assert Counter(two[:super_period]) == want
        assert Counter(two[super_period:]) == want


def test_every_period_holds_its_rollouts():
    period = PARAMS["period"]
    xs = first(5, 40 * period)
    for i in range(0, len(xs), period):
        block = xs[i:i + period]
        assert sum(roll for _, roll in block) == PARAMS["rollouts_per_period"]
        assert all((c in PARAMS["rollout_counts"]) == roll for c, roll in block)


def test_the_shapes_to_warm_follow_from_the_traffic():
    shapes = closed_loop.shapes(PARAMS, {})
    assert {s["evals"] for s in shapes} == {1}  # one operator, one eval
    assert {s["count"] for s in shapes} == set(
        PARAMS["small_counts"]) | set(PARAMS["rollout_counts"])
    bulk = json.loads((BENCH_DIR / "traffic" / "bulk.json").read_text())
    assert [s["evals"] for s in backlog.shapes({**bulk, "jobs": 100}, {})] \
        == list(range(1, 65))  # the worker drains at most 64
    assert [s["evals"] for s in backlog.shapes({**bulk, "jobs": 6}, {})] \
        == list(range(1, 7))
