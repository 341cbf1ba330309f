"""The TPC-H Q1 cell on the CPU at a small size: it resolves by name with
its metrics, a sound run is correct, and its float32 control and a fold
that drops a limb of its sums are not; the fold's metrics read what the
driver records.

The harness's look for a chip is skipped: ``run_cell`` is driven on this
host's devices, the Pallas kernels interpreted."""

import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402

from perfbench import harness, roofline, run  # noqa: E402
from perfbench.fold_bytes import fold_bytes  # noqa: E402
from perfbench.references import tpch_q1 as reference  # noqa: E402

CELL = "tpch_lineitem_q1.q1.client_pallas"
METRICS = {"fold_roofline.agg", "fold_route_pct.agg",
           "predicate_roofline.agg", "dict_decode_roofline.agg",
           "kernel_route_pct.agg", "device_idle_pct.agg",
           "client_cpu_s_per_mrow.agg"}
SEED = 2**31 + 77


def small():
    cell = harness.resolve(harness.load_manifest(ROOT), CELL, ROOT)
    cell.config.update(orders_per_sf=5000, rows_per_object=8000)
    return cell


def run_small(seed=SEED, trace=False):
    return run.run_cell(small(), seed=seed, seconds=0.5, trace=trace,
                        started=time.perf_counter(), devices=jax.devices(),
                        peaks=roofline.PEAKS["TPU v5 lite"])


def test_the_cell_resolves_by_name():
    cell = harness.resolve(harness.load_manifest(ROOT), CELL, ROOT)
    assert cell.chips == 1
    assert cell.config["driver"] == "tpch_q1"
    assert cell.config["table"] == "perfbench/configs/tpch_lineitem.json"
    assert {m["name"] for m in cell.end_to_end} == {
        "scan_rows_per_s", "scan_p90_s", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == METRICS
    assert cell.traffic["decode_backend"] == "pallas"
    assert cell.traffic["parameters"] == {"delta": 90}


@pytest.fixture(scope="module")
def sound():
    return run_small()


def test_sound_run_is_correct_and_its_control_is_not(sound):
    result, checks, driver = sound
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"scan_rows_per_s", "scan_p90_s",
                                      "setup_s"}
    assert driver.notes["objects"] == len(driver.rows) >= 2
    assert 97.0 < driver.notes["kept_pct"] < 99.5
    table = driver.scans[0].table
    assert len(table) == 4
    assert table.column("l_returnflag").values.tolist() == ["A", "N", "N",
                                                            "R"]
    assert table.column("l_linestatus").values.tolist() == ["F", "F", "O",
                                                            "F"]
    control = driver.check(control=True)
    assert not all(c.ok for c in control)
    assert control[0].value == result["attempted"]


def fold_drops_a_limb(monkeypatch):
    """The fold kernel's sums lose their lowest byte."""
    from repro.kernels.group_fold import ops
    join = ops.join_limbs

    def dropped(limbs, terms):
        limbs = limbs.copy()
        limbs.reshape(terms + 1, 8, -1)[:-1, 0] = 0
        return join(limbs, terms)
    monkeypatch.setattr(ops, "join_limbs", dropped)


def fold_in_float32(monkeypatch):
    """The fold kernel's sums pass through float32."""
    from repro.kernels.group_fold import ops
    join = ops.join_limbs

    def rounded(limbs, terms):
        total = join(limbs, terms)
        return total.astype(np.float32).astype(np.int64)
    monkeypatch.setattr(ops, "join_limbs", rounded)


@pytest.mark.parametrize("fault", [fold_drops_a_limb, fold_in_float32],
                         ids=["fold_drops_a_limb", "fold_in_float32"])
def test_broken_fold_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    result, _, _ = run_small()
    assert not result["correct"]
    assert result["checks"]["scans_differing"]["value"] > 0


def test_a_traced_run_reads_the_folds_metrics():
    """On the CPU the trace has no device: the device readings are left
    out; every fold takes the kernel, with the keys as codes."""
    result, _, driver = run_small(trace=True)
    assert result["correct"]
    assert result["metrics"]["fold_route_pct.agg"]["value"] == 100.0
    assert "client_cpu_s_per_mrow.agg" in result["metrics"]
    c = driver.counters()
    folds = c["fold_calls"]
    assert folds and all(f == 4 and g == 4 and o == 8
                         for _, f, g, o in folds)
    scans = [r for r in c["reports"] if "columns" in r]
    assert scans and all(r["codes"] == reference.KEYS for r in scans)
    assert all("l_returnflag" not in r["columns"] for r in scans)


def test_fold_bytes_count_factors_ids_and_sums():
    assert fold_bytes(1000, 4, 4, 8) == 4 * 4000 + 1000 + 8 * 32
    assert fold_bytes(0, 2, 1, 1) == 8


def test_the_folds_metrics_read_recorded_calls():
    roof = harness.metric_reader("fold_roofline.agg", ROOT)
    calls = [(965_000, 4, 4, 8)] * 10
    r = SimpleNamespace(counters={"fold_calls": calls},
                        module_s=lambda m: 0.02 if m == "group_fold"
                        else 0.0, peaks=roofline.PEAKS["TPU v5 lite"])
    want = 100 * 10 * fold_bytes(965_000, 4, 4, 8) / 819e9 / 0.02
    assert roof(r) == pytest.approx(want)
    r.counters = {}
    assert roof(r) is None
    route = harness.metric_reader("fold_route_pct.agg", ROOT)
    reports = [{"columns": {}}, {"fold": "kernel"}, {"fold": "kernel"},
               {"fold": "host:groups"}, {"fold": "kernel"}]
    assert route(SimpleNamespace(counters={"reports": reports})) == 75.0
    # a program without the fold hook reports no fold
    assert route(SimpleNamespace(counters={"reports": reports[:1]})) is None
