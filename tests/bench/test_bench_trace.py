"""The reduction from a profiler trace to busy time, idle share, kernel
time by jitted module, and idle gaps labelled by the host's spans."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from jax.profiler import ProfileData  # noqa: E402

from perfbench import tracereduce as tr  # noqa: E402

SLICE = Path(__file__).resolve().parent / "data" / "v5e_scan_slice.json"


def xspace(planes: dict) -> ProfileData:
    """A ProfileData from ``{plane: {line: [(name, start_ns, dur_ns)]}}``,
    written as the XSpace text form the profiler's own files parse to."""
    out = []
    for pid, (plane, lines) in enumerate(planes.items(), 1):
        names = sorted({e[0] for evs in lines.values() for e in evs})
        ids = {n: i for i, n in enumerate(names, 1)}
        body = [f'id: {pid}', f'name: "{plane}"']
        for lid, (line, evs) in enumerate(lines.items(), 1):
            ev = " ".join(
                f"events {{ metadata_id: {ids[n]} offset_ps: {int(s * 1000)}"
                f" duration_ps: {int(d * 1000)} }}" for n, s, d in evs)
            body.append(f'lines {{ id: {lid} name: "{line}" '
                        f'timestamp_ns: 0 {ev} }}')
        body += [f'event_metadata {{ key: {i} value {{ id: {i} '
                 f'name: "{n}" }} }}' for n, i in ids.items()]
        out.append("planes { " + " ".join(body) + " }")
    return ProfileData.from_text_proto("\n".join(out))


def small_trace():
    return tr.from_profile(xspace({
        "/device:TPU:0": {
            "XLA Ops": [("gather", 1000, 400), ("fusion", 1200, 400),
                        ("token_pack", 3000, 1000), ("copy", 9500, 2000)],
            "XLA Modules": [("jit_dict_decode(12)", 1000, 600),
                            ("jit__pack(3)", 3000, 1000),
                            ("jit__pack(3)", 9500, 2000)],
        },
        "/device:TPU:1": {"XLA Ops": [("copy", 5000, 1000)]},
        "/host:CPU": {
            "main": [("bench.window", 500, 9500), ("other", 600, 100)],
            "worker0": [("bench.scan", 800, 8900)],
            "worker1": [("bench.scan", 4500, 5000)],
        },
    }))


def test_parse_keeps_device_lines_and_bench_spans():
    t = small_trace()
    assert sorted(t.ops) == [0, 1] and sorted(t.modules) == [0]
    assert {s.name for s in t.spans} == {"bench.window", "bench.scan"}
    assert t.window() == (500, 10000)


def test_busy_is_the_union_clipped_to_the_window():
    t = small_trace()
    lo, hi = t.window()
    # [1000, 1600) once though two ops overlap, [3000, 4000), and the
    # copy from 9500 clipped at 10000
    assert tr.busy_s(t.ops[0], lo, hi) == pytest.approx(2100e-9)
    s = tr.summarize(t, [0])
    assert s.window_s == pytest.approx(9500e-9)
    assert s.idle_pct() == pytest.approx(100 * (1 - 2100 / 9500))


def test_kernel_time_by_module_counts_runs_started_in_the_window():
    t = small_trace()
    lo, hi = t.window()
    assert tr.module_name("jit_dict_decode(12)") == "dict_decode"
    assert tr.module_name("jit__pack(3)") == "_pack"
    assert tr.module_s(t, "dict_decode", lo, hi) == pytest.approx(600e-9)
    assert tr.module_s(t, "_pack", lo, hi) == pytest.approx(3000e-9)
    assert tr.module_s(t, "_pack", lo, 9000) == pytest.approx(1000e-9)


def test_idle_gaps_are_labelled_by_the_spans_open_over_them():
    s = tr.summarize(small_trace(), [0])
    gaps = {round(g, 12): name for name, g in s.idle_gaps}
    assert gaps[5500e-9] == "bench.scanx2"        # 4000..9500
    assert gaps[1400e-9] == "bench.scan"          # 1600..3000
    assert gaps[500e-9] == "no bench span"        # 500..1000
    assert s.device_ops[0] == ["_pack/token_pack", pytest.approx(1000e-9)]


def test_several_devices_are_named_and_averaged():
    s = tr.summarize(small_trace(), [0, 1])
    assert s.busy_s == pytest.approx({0: 2100e-9, 1: 1000e-9})
    assert s.mean_busy_s == pytest.approx(1550e-9)
    assert s.idle_pct(1) == pytest.approx(100 * (1 - 1000 / 9500))
    assert all(n.startswith(("TPU0 ", "TPU1 ")) for n, _ in s.idle_gaps)
    assert "TPU1 ?/copy" in dict(s.device_ops)
    assert "TPU0 dict_decode/gather" in dict(s.device_ops)


def test_a_window_span_is_required():
    t = tr.from_profile(xspace({"/host:CPU": {"main": [("x", 0, 1)]}}))
    with pytest.raises(ValueError):
        t.window()


def test_recorded_v5e_slice():
    """Three milliseconds of a real trace of the Pallas scan cell on one
    v5e: its device operations, jitted modules and the scan spans."""
    rec = json.loads(SLICE.read_text())
    planes = {"/device:TPU:0": {
        "XLA Ops": [(n, a, b - a) for n, a, b in rec["ops"]],
        "XLA Modules": [(n, a, b - a) for n, a, b in rec["modules"]]},
        "/host:CPU": {f"t{i}": [(n, a, b - a)]
                      for i, (n, a, b) in enumerate(rec["spans"])}}
    planes["/host:CPU"]["main"] = [("bench.window", rec["lo"],
                                    rec["hi"] - rec["lo"])]
    t = tr.from_profile(xspace(planes))
    lo, hi = t.window()
    s = tr.summarize(t, [0])
    assert 0 < s.busy_s[0] <= s.window_s
    assert 0 <= s.idle_pct() < 100
    union = tr.union(t.ops[0], lo, hi)
    assert all(a < b <= c for (a, b), (c, _) in zip(union, union[1:]))
    assert tr.module_s(t, "dict_decode", lo, hi) > 0
    names = dict(s.device_ops)
    assert "dict_decode/%fusion" in names and "_pack/%pad.1" not in names
    assert "_pad/%pad.1" in names
    assert sum(g for _, g in s.idle_gaps) <= s.window_s - s.busy_s[0] \
        + 1e-12
