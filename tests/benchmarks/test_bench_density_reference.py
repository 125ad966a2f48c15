"""The density reference is arithmetic; hold it to a brute-force packer
on cases small enough to enumerate."""

import itertools

import pytest

from benchmarks.reference import density


def brute_force_min_nodes(placed_by_dc: dict, per_node: int) -> int:
    """Least nodes over all ways to deal each datacenter's allocs onto
    its nodes, by trying every node count upward."""
    total = 0
    for n in placed_by_dc.values():
        if n == 0:
            continue
        for nodes in itertools.count(1):
            # can `n` identical allocs be dealt onto `nodes` nodes of
            # `per_node` slots? enumerate the fills of the first node
            def fits(left, k):
                if left == 0:
                    return True
                if k == 0:
                    return False
                return any(fits(left - take, k - 1)
                           for take in range(min(left, per_node), 0, -1))
            if fits(n, nodes):
                total += nodes
                break
    return total


@pytest.mark.parametrize("per_node", [1, 3, 16])
@pytest.mark.parametrize("placed", [
    {"dc1": 0}, {"dc1": 1}, {"dc1": 16}, {"dc1": 17},
    {"dc1": 5, "dc2": 7}, {"dc1": 33, "dc2": 1, "dc3": 16, "dc4": 0},
])
def test_ideal_nodes_is_what_a_brute_force_packer_finds(placed, per_node):
    assert density.ideal_nodes(placed, per_node) == brute_force_min_nodes(
        placed, per_node)


def test_allocs_per_node_takes_the_tightest_resource():
    node = {"cpu_mhz": 4000, "memory_mb": 8192, "disk_mb": 102400}
    assert density.allocs_per_node(
        node, {"cpu_mhz": 250, "memory_mb": 128, "disk_mb": 300}) == 16
    assert density.allocs_per_node(
        node, {"cpu_mhz": 20, "memory_mb": 40, "disk_mb": 300}) == 200
    assert density.allocs_per_node(
        node, {"cpu_mhz": 20, "memory_mb": 40, "disk_mb": 1024}) == 100


def test_packing_share_is_not_clipped():
    """250 ideal over 252 touched is 99.2, and fewer nodes touched than
    the reference thinks possible reads over 100 (which run.py turns into
    `correct: false`), not a flat 100."""
    nodes = [{"id": f"n{i}", "datacenter": "dc1"} for i in range(300)]
    allocs = [{"node": f"n{i % 252}"} for i in range(4000)]
    got = density.packing({"nodes": nodes, "allocs": allocs}, 16)
    assert got["ideal_nodes"] == 250 and got["nodes_touched"] == 252
    assert got["packing_share"] == pytest.approx(100 * 250 / 252)
    crowded = [{"node": f"n{i % 200}"} for i in range(4000)]  # 20 a node
    over = density.packing({"nodes": nodes, "allocs": crowded}, 16)
    assert over["packing_share"] == pytest.approx(125.0)
