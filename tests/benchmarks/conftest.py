"""One expected failure, said where every run of the tests reads it.

`test_bench_failure_rules.py::test_every_rule_a_configuration_names_is_one_of_the_files`
pins the LISTING of `benchmarks/reference/rules/` to the six rules PR 26
made. PR 27 adds a deployment whose guarantees bring two rules of their
own (`preemption_lowest_first`, `standing_held_or_evicted`), as
`benchmarks/README.md` says a configuration does — and a PR that adds to
the benchmark may edit no file the benchmark already has, that test
among them. So the test is marked an expected failure here, by name and
for that one reason; what it guards beyond the listing (the six rules
exist, `c1m-5k` and `c2m-10k` name exactly those, `store_check.py` is
gone) is asserted anew in
`test_bench_tiers.py::test_the_six_rules_stand_and_every_rule_file_is_named`.
The mark is strict and for an AssertionError alone: the day the pin is
loosened to a superset the test passes, the strict mark fails the run,
and this file has to go. The next `benchmark` PR does both (ROADMAP R0).
"""

import pytest

PINNED = ("test_bench_failure_rules.py::"
          "test_every_rule_a_configuration_names_is_one_of_the_files")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(PINNED):
            item.add_marker(pytest.mark.xfail(
                reason="pins the listing of reference/rules/ to PR 26's "
                       "six; PR 27's deployment brings two rules and may "
                       "not edit this file (tests/benchmarks/conftest.py)",
                raises=AssertionError, strict=True))
