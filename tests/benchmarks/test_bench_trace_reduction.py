"""The reduction from a profiler trace to device numbers
(benchmarks/harness/xplane.py, the `trace_busy` and `roofline` reducers)
held to a small trace recorded on the chip: three runs of the placement
kernel with the device idle before, between and after, and the host's
monotonic stamps of the same moments (benchmarks/fixtures/)."""

import json
from pathlib import Path

import pytest

from benchmarks.harness import kernel_cost, peaks, spec, xplane

FIX = Path(__file__).resolve().parents[2] / "benchmarks" / "fixtures"
KERNEL = "solve_placement_compact"


@pytest.fixture(scope="module")
def recorded():
    stamps = json.loads((FIX / "small.json").read_text())
    trace = xplane.read(FIX / "small.xplane.pb")
    assert trace["sync_ns"] is not None, trace["planes"]
    offset = trace["sync_ns"] - stamps["sync_mono_ns"]
    spans = [(n, s + offset, e + offset) for n, s, e in stamps["spans"]]
    return trace, stamps, offset, spans


def test_the_fixture_is_a_chip_trace(recorded):
    trace, stamps, _, _ = recorded
    assert stamps["device"]["platform"] == "tpu"
    assert peaks.peak(stamps["device"]["kind"])["hbm_bytes_per_s"] == 819e9
    assert list(trace["devices"]) == ["/device:TPU:0"]
    dev = trace["devices"]["/device:TPU:0"]
    assert dev["ops"] and dev["modules"]


def test_busy_is_inside_the_hosts_solves_and_the_rest_is_idle(recorded):
    trace, stamps, offset, spans = recorded
    t0, t1 = trace["sync_ns"], stamps["end_mono_ns"] + offset
    b = xplane.busy(trace, t0, t1)
    host_solve_s = sum(e - s for _, s, e in spans) / 1e9
    assert 0 < b["busy_s"] < host_solve_s  # the device ran inside the calls
    assert b["window_s"] == pytest.approx((t1 - t0) / 1e9)
    assert b["idle_share"] == pytest.approx(
        100 * (1 - b["busy_s"] / b["window_s"]))
    assert 90 < b["idle_share"] < 100
    # every op lies inside one of the host's solve spans
    for _, s, e in trace["devices"]["/device:TPU:0"]["ops"]:
        assert any(hs <= s and e <= he for _, hs, he in spans)


def test_a_window_in_which_the_device_ran_nothing_reads_idle_100(recorded):
    trace, stamps, offset, spans = recorded
    quiet_end = spans[0][1]  # before the first solve began
    b = xplane.busy(trace, trace["sync_ns"], quiet_end)
    assert b["busy_s"] == 0.0 and b["idle_share"] == 100.0
    assert xplane.busy(trace, quiet_end, quiet_end)["idle_share"] == 100.0
    gaps = xplane.idle_gaps(trace, trace["sync_ns"], quiet_end, spans)
    assert gaps == [("unattributed",
                     pytest.approx((quiet_end - trace["sync_ns"]) / 1e9))]
    reducer = spec.load_module("reducers", "trace_busy")
    quiet = {"device": {"idle_share": b["idle_share"], "modules": {}}}
    assert reducer.reduce(quiet, {"what": "idle_share"}, {}) == 100.0
    # nothing to read is no metric, not a zero and not a division by zero
    assert reducer.reduce(quiet, {"what": "module_ms", "module": KERNEL},
                          {}) is None
    assert reducer.reduce({}, {"what": "idle_share"}, {}) is None


def test_the_kernels_module_ran_three_times_and_its_ops_lead_the_list(recorded):
    trace, stamps, offset, spans = recorded
    t0, t1 = trace["sync_ns"], stamps["end_mono_ns"] + offset
    durs = xplane.module_seconds(trace, t0, t1, KERNEL)
    assert len(durs) == 3 and all(d > 0 for d in durs)
    busy_s = xplane.busy(trace, t0, t1)["busy_s"]
    assert busy_s <= sum(durs) * 1.001  # ops run inside their module
    top = xplane.top_ops(trace, t0, t1)
    assert 1 <= len(top) <= 10
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    assert sum(s for _, s in top) <= busy_s * 1.001


def test_idle_gaps_go_to_the_host_span_that_covers_them(recorded):
    trace, stamps, offset, spans = recorded
    t0, t1 = trace["sync_ns"], stamps["end_mono_ns"] + offset
    gaps = dict(xplane.idle_gaps(trace, t0, t1, spans))
    b = xplane.busy(trace, t0, t1)
    assert sum(gaps.values()) == pytest.approx(b["window_s"] - b["busy_s"])
    host_solve_s = sum(e - s for _, s, e in spans) / 1e9
    # inside a solve the device idles while the host dispatches and reads
    # back; the sleeps between solves are covered by no span
    assert gaps["solve"] == pytest.approx(host_solve_s - b["busy_s"])
    assert gaps["unattributed"] > gaps["solve"]
    inner = [("inner", spans[1][1] + 1000, spans[1][2])]  # started later: wins
    both = dict(xplane.idle_gaps(trace, t0, t1, spans + inner))
    assert both["inner"] > 0
    assert both["inner"] + both["solve"] == pytest.approx(gaps["solve"])


@pytest.mark.parametrize("n_spans, uncovered_ns, names", [
    # beyond the tenth: it takes the last place, and s9 gives way
    (12, 5, [f"s{i}" for i in range(9)] + ["unattributed"]),
    # among the longest anyway: nothing is pushed out
    (12, 5000, ["unattributed"] + [f"s{i}" for i in range(9)]),
    (3, 5, ["s0", "s1", "s2", "unattributed"]),
])
def test_what_no_span_covers_is_always_among_the_idle_gaps(
        n_spans, uncovered_ns, names):
    """A window the device idles through, host spans of falling length
    side by side, and some ns that no span covers."""
    quiet = {"devices": {"dev": {"ops": []}}}
    spans, at = [], 0
    for i in range(n_spans):
        spans.append((f"s{i}", at, at + 1000 - 10 * i))
        at += 1000 - 10 * i
    gaps = xplane.idle_gaps(quiet, 0, at + uncovered_ns, spans)
    assert [n for n, _ in gaps] == names
    assert dict(gaps)["unattributed"] == pytest.approx(uncovered_ns / 1e9)


def test_roofline_share_of_the_recorded_solves(recorded):
    trace, stamps, offset, spans = recorded
    t0, t1 = trace["sync_ns"], stamps["end_mono_ns"] + offset
    durs = xplane.module_seconds(trace, t0, t1, KERNEL)
    samples = {"device": {"modules": {KERNEL: durs}},
               "timings": {"nomad.tpu.solve_groups": [8.0, 8.0, 8.0]}}
    metric = {"module": KERNEL, "bytes_fn": "compact_solve_bytes",
              "reads": {"timings": ["nomad.tpu.solve_groups"]}}
    ctx = {"config": {"nodes": 256}, "device_kind": stamps["device"]["kind"]}
    share = spec.load_module("reducers", "roofline").reduce(
        samples, metric, ctx)
    need = 3 * kernel_cost.compact_solve_bytes(256, 8)
    assert need == 3 * 51 * 256 * 8
    assert share == pytest.approx(100 * need / 819e9 / sum(durs))
    assert 0 < share < 100
    with pytest.raises(KeyError):
        peaks.peak("TPU v9000")
    # nothing traced, or no solve reported: no metric
    assert spec.load_module("reducers", "roofline").reduce(
        {"device": {"modules": {}}}, metric, ctx) is None


def test_union_and_clip():
    assert xplane.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert xplane.clip([(1, 4), (5, 8)], 3, 6) == [(3, 4), (5, 6)]
    assert xplane.busy({"devices": {}}, 0, 10)["idle_share"] == 100.0


def test_run_py_reads_a_trace_directory_into_the_line(tmp_path, recorded):
    """run.py's own step from a profiler directory to `device`,
    `breakdown` and the samples the reducers read."""
    import shutil

    from benchmarks import run as bench_run

    _, stamps, _, _ = recorded
    at = tmp_path / "plugins" / "profile" / "t"
    at.mkdir(parents=True)
    shutil.copy(FIX / "small.xplane.pb", at / "small.xplane.pb")
    samples = {"spans": {"solve": [(s, e) for _, s, e in stamps["spans"]],
                         "eval": [(stamps["sync_mono_ns"],
                                   stamps["end_mono_ns"])]}}
    device, report = {}, {}
    breakdown = bench_run._read_trace(
        tmp_path, {"mono_ns": stamps["sync_mono_ns"],
                   "close_mono_ns": stamps["end_mono_ns"]},
        {KERNEL}, samples, device, report)
    assert 0 < device["busy_s"] < device["window_s"]
    assert len(samples["device"]["modules"][KERNEL]) == 3
    assert [n for n, _ in breakdown["idle_gaps"]] == ["unattributed", "solve"]
    assert 1 <= len(breakdown["device_ops"]) <= 10
    # ops are named by their short names, and a loop's own time leaves
    # out the ops of its body
    assert all(" " not in n and not n.startswith("%")
               for n, _ in breakdown["device_ops"])
    assert sum(s for _, s in breakdown["device_ops"]) <= device["busy_s"]
