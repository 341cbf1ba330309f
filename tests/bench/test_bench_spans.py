"""The reduction of the program's host spans (``perfbench/spans.py``):
self time less nested spans, clipped to the window; idle gaps named by
the innermost span of each thread; idle time no stage span covers.  With
only the benchmark's spans it reads as ``tracereduce`` does."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from test_bench_trace import SLICE, small_trace, xspace  # noqa: E402

from perfbench import spanreport, spans  # noqa: E402
from perfbench import tracereduce as tr  # noqa: E402

# window [1000, 11000); device busy [2000, 3000) and [7000, 7500)
NESTED = {
    "/device:TPU:0": {"XLA Ops": [("op", 2000, 1000), ("op2", 7000, 500)]},
    "/host:CPU": {
        "main": [("bench.window", 1000, 10000)],
        "w0": [("bench.scan", 500, 10000), ("repro.scan.task", 800, 9200),
               ("repro.storage.read", 900, 1000),
               ("repro.decode.decompress", 2000, 2000),
               ("repro.kernel.dict_decode", 4000, 4000),
               ("repro.kernel.fetch", 5000, 2000),
               ("repro.decode.host", 8000, 1500)],
        "w1": [("bench.scan", 3000, 8500), ("repro.scan.task", 3100, 8400),
               ("repro.storage.read", 3200, 2800),
               ("repro.kernel.pack", 6000, 4000),
               ("repro.kernel.fetch", 6500, 2500)],
    },
}

#: self ns of each reading in NESTED, summed over both threads
SELF_NS = {"storage_read_s_per_mrow.scan": 900 + 2800,
           "decompress_s_per_mrow.scan": 2000,
           "host_decode_s_per_mrow.scan": 1500,
           "kernel_stage_s_per_mrow.scan": (4000 - 2000) + (4000 - 2500),
           "kernel_fetch_s_per_mrow.scan": 2000 + 2500}
TASK_SELF_NS = (9000 - 900 - 2000 - 4000 - 1500) + (7900 - 2800 - 4000)


def nested():
    profile = xspace(NESTED)
    return tr.from_profile(profile), spans.from_profile(profile)


def test_spans_keep_their_thread_and_both_prefixes():
    _, host = nested()
    assert {s.thread for s in host} == {0, 1, 2}
    assert {s.thread for s in host if s.name.startswith("repro.")} == {1, 2}
    assert len(host) == 1 + 7 + 5


def test_label_names_each_threads_innermost_span():
    _, host = nested()
    assert spans.label(host, 1500) == "repro.storage.read"
    assert spans.label(host, 5000) == "repro.kernel.fetch+repro.storage.read"
    assert spans.label(host, 9250) == "repro.decode.host+repro.kernel.pack"
    assert spans.label(host, 10250) == "bench.scan+repro.scan.task"
    assert spans.label(host, 600) == "bench.scan"
    assert spans.label(host, 20000) == "no bench span"


def test_idle_gaps_carry_the_stage_labels():
    trace, host = nested()
    assert spans.idle_gaps(trace, host, [0]) == [
        ["repro.kernel.fetch+repro.storage.read", pytest.approx(4000e-9)],
        ["repro.decode.host+repro.kernel.pack", pytest.approx(3500e-9)],
        ["repro.storage.read", pytest.approx(1000e-9)]]


def test_self_time_is_less_nested_spans_and_clipped_to_the_window():
    trace, host = nested()
    lo, hi = trace.window()
    own = {(s.thread, s.name): ns for s, ns in spans.self_ns(host, lo, hi)}
    assert own[(1, "repro.scan.task")] == 9000 - 900 - 2000 - 4000 - 1500
    assert own[(2, "repro.scan.task")] == 7900 - 2800 - 4000
    assert own[(1, "repro.storage.read")] == 900       # from 900, lo 1000
    assert own[(1, "repro.kernel.dict_decode")] == 2000
    assert own[(2, "repro.kernel.pack")] == 1500
    assert own[(1, "bench.scan")] == 9500 - 9000


@pytest.mark.parametrize("reading", sorted(spans.STAGES))
def test_each_stage_reading_per_mrow(reading):
    trace, host = nested()
    lo, hi = trace.window()
    got = spans.self_s_per_mrow(host, lo, hi, 2_000_000)[reading]
    assert got == pytest.approx(SELF_NS[reading] / 1e9 / 2)


def test_stage_readings_and_task_self_time_add_up_to_the_task():
    trace, host = nested()
    out = spanreport.reduce(trace, host, 2_000_000, [0])
    stages = sum(out[r] for r in spans.STAGES)
    assert stages + out["task_self_s_per_mrow"] == pytest.approx(
        out["task_s_per_mrow"])
    assert out["task_s_per_mrow"] == pytest.approx((9000 + 7900) / 1e9 / 2)
    assert out["task_self_s_per_mrow"] == pytest.approx(
        TASK_SELF_NS / 1e9 / 2)
    assert out["admit_s_per_mrow"] is None
    assert out["idle_gaps"][0][0] == "repro.kernel.fetch+repro.storage.read"
    json.dumps(out)


def test_readings_are_none_without_rows_or_spans():
    trace, host = nested()
    lo, hi = trace.window()
    assert set(spans.self_s_per_mrow(host, lo, hi, 0).values()) == {None}
    bench_only = [s for s in host if s.name.startswith("bench.")]
    assert set(spans.self_s_per_mrow(bench_only, lo, hi, 10).values()) \
        == {None}


def test_unattributed_idle_is_idle_time_no_stage_span_covers():
    trace, host = nested()
    # idle 1000 + 4000 + 3500; stages cover [1000, 1900) and
    # [2000, 10000): 7400 of it
    assert spans.unattributed_idle_pct(trace, host, 0) == pytest.approx(
        100 * 1100 / 8500)
    bench_only = [s for s in host if s.name.startswith("bench.")]
    assert spans.unattributed_idle_pct(trace, bench_only, 0) == \
        pytest.approx(100.0)


@pytest.mark.parametrize("devices", [[0], [0, 1]])
def test_with_bench_spans_only_the_gaps_read_as_tracereduce_does(devices):
    profile_trace = small_trace()
    host = [spans.Span(s.name, s.start_ns, s.end_ns, i)
            for i, s in enumerate(profile_trace.spans)]
    summary = tr.summarize(profile_trace, devices)
    assert spans.idle_gaps(profile_trace, host, devices) == summary.idle_gaps
    for _, (a, b) in zip(range(3), tr.gaps(profile_trace.ops[0],
                                           *profile_trace.window())):
        t = (a + b) / 2
        assert spans.label(host, t) == tr.label(profile_trace.spans, t)


def slice_profile():
    rec = json.loads(SLICE.read_text())
    planes = {"/device:TPU:0": {
        "XLA Ops": [(n, a, b - a) for n, a, b in rec["ops"]],
        "XLA Modules": [(n, a, b - a) for n, a, b in rec["modules"]]},
        "/host:CPU": {f"t{i}": [(n, a, b - a)]
                      for i, (n, a, b) in enumerate(rec["spans"])}}
    planes["/host:CPU"]["main"] = [("bench.window", rec["lo"],
                                    rec["hi"] - rec["lo"])]
    return xspace(planes)


def test_recorded_v5e_slice_reads_as_before():
    """The recorded slice's idle share, kernel times and gap labels, as
    ``tracereduce`` read them before the program had spans."""
    profile = slice_profile()
    trace, host = tr.from_profile(profile), spans.from_profile(profile)
    lo, hi = trace.window()
    s = tr.summarize(trace, [0])
    assert s.idle_pct() == pytest.approx(49.30113333333333, rel=1e-12)
    assert tr.module_s(trace, "dict_decode", lo, hi) == pytest.approx(
        0.014425923, rel=1e-12)
    assert tr.module_s(trace, "_pad", lo, hi) == pytest.approx(
        2.1332e-05, rel=1e-12)
    assert tr.module_s(trace, "_pack", lo, hi) == 0
    assert spans.idle_gaps(trace, host, [0]) == s.idle_gaps
    assert {g for g, _ in s.idle_gaps} == {"bench.scanx4"}
    assert spans.self_s_per_mrow(host, lo, hi, 1_000_000) == {
        r: None for r in spans.STAGES}
