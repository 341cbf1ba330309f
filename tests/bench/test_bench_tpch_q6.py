"""The TPC-H Q6 cell on the CPU at a small size: it resolves by name, a
sound run is correct and its float32 control is not, a run with its timed
path broken is not, and the generator keeps the rules of TPC-H's Clause
4.2.3.

The harness's look for a chip is skipped: ``run_cell`` is driven on this
host's devices, the Pallas kernels interpreted."""

import datetime
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402

from perfbench import harness, roofline, run, tpch  # noqa: E402
from perfbench.drivers import tpch_agg  # noqa: E402
from perfbench.predicate_bytes import predicate_bytes  # noqa: E402
from perfbench.references import tpch_q6 as reference  # noqa: E402

CELL = "tpch_lineitem.q6.client_pallas"
METRICS = {"predicate_roofline.agg", "dict_decode_roofline.agg",
           "kernel_route_pct.agg", "device_idle_pct.agg",
           "client_cpu_s_per_mrow.agg"}
CONFIG = harness.load_json(ROOT / "perfbench" / "configs" /
                           "tpch_lineitem.json")
SEED = 2**31 + 40
DAY = tpch.days


def small():
    cell = harness.resolve(harness.load_manifest(ROOT), CELL, ROOT)
    cell.config.update(orders_per_sf=5000, rows_per_object=8000)
    return cell


def run_small(seed=SEED):
    return run.run_cell(small(), seed=seed, seconds=0.5, trace=False,
                        started=time.perf_counter(), devices=jax.devices(),
                        peaks=roofline.PEAKS["TPU v5 lite"])


def test_the_cell_resolves_by_name():
    cell = harness.resolve(harness.load_manifest(ROOT), CELL, ROOT)
    assert cell.chips == 1
    assert cell.config["driver"] == "tpch_agg"
    assert {m["name"] for m in cell.end_to_end} == {
        "scan_rows_per_s", "scan_p90_s", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == METRICS
    assert cell.traffic["decode_backend"] == "pallas"
    assert cell.traffic["parameters"] == {"date": "1994-01-01",
                                          "discount": "0.06",
                                          "quantity": 24}


def test_sound_run_is_correct_and_its_control_is_not():
    result, checks, driver = run_small()
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 0
    assert [c.name for c in checks] == ["scans_differing", "scans_failed"]
    assert set(result["metrics"]) == {"scan_rows_per_s", "scan_p90_s",
                                      "setup_s"}
    # objects hold whole orders, so each counts its own rows
    assert driver.notes["objects"] == len(driver.rows) >= 2
    assert all(r <= 8000 for r in driver.rows)
    assert driver.notes["rows"] == sum(driver.rows)
    assert driver.rows_scanned == sum(driver.rows[s.obj]
                                      for s in driver.scans)
    assert result["metrics"]["scan_rows_per_s"]["value"] > 0
    control = driver.check(control=True)
    assert not all(c.ok for c in control)
    assert control[0].value == result["attempted"]


def test_a_traced_run_reads_the_cells_metrics():
    """On the CPU the trace has no device: the device readings are left
    out, the program's counters are not, and every route is a kernel's
    but the two host-decoded columns of a small object."""
    result, _, driver = run.run_cell(
        small(), seed=SEED, seconds=0.5, trace=True,
        started=time.perf_counter(), devices=jax.devices(),
        peaks=roofline.PEAKS["TPU v5 lite"])
    assert result["correct"]
    assert {"kernel_route_pct.agg",
            "client_cpu_s_per_mrow.agg"} <= set(result["metrics"])
    calls = driver.counters()["predicate_calls"]
    assert calls and {k for _, k in calls} == {3}
    assert {n for n, _ in calls} <= set(driver.rows)


_q6_filter = tpch_agg.q6_filter


def _upper_ship_date_inclusive(params):
    from repro.aformat.expressions import Cmp

    lo = datetime.date.fromisoformat(params["date"])
    pred = _q6_filter(params)
    # ((((ship >= lo) & (ship < hi)) & ...) ...): the second leaf
    node = pred
    while not isinstance(node.lhs, Cmp):
        node = node.lhs
    assert node.rhs.op == "<" and node.rhs.column == "l_shipdate"
    node.rhs = Cmp("<=", "l_shipdate", lo.replace(year=lo.year + 1))
    return pred


def upper_ship_date_inclusive(monkeypatch):
    """``<=`` in place of ``<`` on the upper ship date."""
    monkeypatch.setattr(tpch_agg, "q6_filter", _upper_ship_date_inclusive)


def fold_drops_a_row(monkeypatch):
    """The client's fold leaves out each task's last matching row."""
    from repro.dataset import format as fmt
    fold = fmt.partial_aggregate

    def dropped(tbl, specs, group_by=None, **kw):
        return fold(tbl.slice(0, max(len(tbl) - 1, 0)), specs, group_by,
                    **kw)
    monkeypatch.setattr(fmt, "partial_aggregate", dropped)


@pytest.mark.parametrize("fault", [upper_ship_date_inclusive,
                                   fold_drops_a_row],
                         ids=["upper_ship_date_inclusive",
                              "fold_drops_a_row"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    result, _, driver = run_small()
    if fault is upper_ship_date_inclusive:
        # the fault shows only where a matching line ships on the bound
        b = reference.bounds(driver.params)
        on_bound = sum(int(np.sum(
            (d["l_shipdate"] == b["ship_hi"])
            & (d["l_discount"] >= b["disc_lo"])
            & (d["l_discount"] <= b["disc_hi"])
            & (d["l_quantity"] < b["qty_hi"]))) for d in driver.data)
        assert on_bound > 0
    assert not result["correct"]
    assert result["checks"]["scans_differing"]["value"] > 0


# -- the generator ------------------------------------------------------------

def objects(seed, orders=5000, rows_per_object=8000):
    cfg = dict(CONFIG, orders_per_sf=orders)
    o = tpch.orders(cfg, seed)
    pool = tpch.text_pool(seed)
    spans = tpch.object_orders(o["lines"], rows_per_object)
    return o, spans, [tpch.lineitem(cfg, o, s, seed, i, pool)
                      for i, s in enumerate(spans)]


def test_lines_keep_the_rules_of_clause_4_2_3():
    o, spans, objs = objects(2**35 + 1)
    cols = {k: np.concatenate([c[k] for c in objs]) for k in objs[0]}
    assert list(objs[0]) == [c["name"] for c in CONFIG["columns"]]
    n = len(cols["l_orderkey"])
    odate = np.repeat(o["orderdate"], o["lines"])
    # orders: sparse keys, dates, 1 to 7 lines numbered from 1
    assert np.all(o["orderkey"] % 32 >= 1) and np.all(o["orderkey"] % 32 <= 8)
    assert o["orderdate"].min() >= DAY(datetime.date(1992, 1, 1))
    assert o["orderdate"].max() <= DAY(datetime.date(1998, 8, 2))
    assert set(o["lines"].tolist()) == set(range(1, 8))
    assert np.array_equal(cols["l_orderkey"], np.repeat(o["orderkey"],
                                                        o["lines"]))
    assert cols["l_linenumber"].min() == 1
    assert cols["l_linenumber"].max() == 7
    # keys and the supplier formula
    pk, sk = cols["l_partkey"], cols["l_suppkey"]
    assert pk.min() >= 1 and pk.max() <= 200_000
    s = 10_000
    i = [(pk + j * (s // 4 + (pk - 1) // s)) % s + 1 for j in range(4)]
    assert np.all(np.any(np.stack(i) == sk, axis=0))
    # decimals, in cents: quantity, price formula, discount, tax
    qty = cols["l_quantity"]
    assert set(np.unique(qty // 100).tolist()) == set(range(1, 51))
    assert np.all(qty % 100 == 0)
    retail = 90_000 + (pk // 10) % 20_001 + 100 * (pk % 1000)
    assert np.array_equal(cols["l_extendedprice"], qty // 100 * retail)
    assert set(np.unique(cols["l_discount"]).tolist()) == set(range(11))
    assert set(np.unique(cols["l_tax"]).tolist()) == set(range(9))
    # dates as offsets from the order date
    ship, commit = cols["l_shipdate"], cols["l_commitdate"]
    receipt = cols["l_receiptdate"]
    assert (ship - odate).min() == 1 and (ship - odate).max() == 121
    assert (commit - odate).min() == 30 and (commit - odate).max() == 90
    assert (receipt - ship).min() == 1 and (receipt - ship).max() == 30
    for d in (ship, commit, receipt):
        assert d.dtype == np.int32
    # flags against CURRENTDATE
    current = DAY(datetime.date(1995, 6, 17))
    flag, status = cols["l_returnflag"], cols["l_linestatus"]
    assert set(flag[receipt <= current]) == {"R", "A"}
    assert set(flag[receipt > current]) == {"N"}
    assert set(status[ship > current]) == {"O"}
    assert set(status[ship <= current]) == {"F"}
    assert set(cols["l_shipinstruct"]) == set(tpch.INSTRUCTIONS)
    assert set(cols["l_shipmode"]) == set(tpch.MODES)
    lens = np.array([len(c) for c in cols["l_comment"]])
    assert lens.min() == 10 and lens.max() == 43
    assert len(set(cols["l_comment"])) > 0.99 * n


@pytest.mark.parametrize("seed", [0, 2**31 + 3, 2**40 + 9])
def test_about_four_lines_an_order_and_whole_orders_an_object(seed):
    o, spans, objs = objects(seed, orders=20_000, rows_per_object=30_000)
    rows = [len(c["l_orderkey"]) for c in objs]
    assert 3.95 < sum(rows) / 20_000 < 4.05
    assert all(r <= 30_000 for r in rows) and rows[-1] < 30_000
    assert len(rows) == -(-sum(rows) // 30_000)
    # each order's lines lie in one object, in orderkey order
    keys = [c["l_orderkey"] for c in objs]
    assert all(a[-1] < b[0] for a, b in zip(keys, keys[1:]))
    assert [hi - lo for lo, hi in spans] == [len(np.unique(k)) for k in keys]


def test_the_same_seed_makes_the_same_objects():
    a = objects(2**33)[2]
    b = objects(2**33)[2]
    c = objects(2**33 + 1)[2]
    for k in a[1]:
        assert list(a[1][k]) == list(b[1][k])
    assert a[1]["l_extendedprice"].tobytes() != \
        c[1]["l_extendedprice"].tobytes()
    assert a[0]["l_partkey"].tobytes() != a[1]["l_partkey"][
        :len(a[0]["l_partkey"])].tobytes()


# -- the metrics' readers -----------------------------------------------------

def test_predicate_bytes_count_columns_read_and_a_byte_mask():
    assert predicate_bytes(1000, 3) == 3 * 4000 + 1000
    assert predicate_bytes(0, 3) == 0


def test_the_predicates_roofline_reads_recorded_calls():
    read = harness.metric_reader("predicate_roofline.agg", ROOT)
    calls = [(979_691, 3)] * 10
    r = SimpleNamespace(counters={"predicate_calls": calls},
                        module_s=lambda m: 0.01 if m == "predicate_mask"
                        else 0.0, peaks=roofline.PEAKS["TPU v5 lite"])
    want = 100 * 10 * predicate_bytes(979_691, 3) / 819e9 / 0.01
    assert read(r) == pytest.approx(want)
    # a program that records no predicate calls reads nothing
    r.counters = {}
    assert read(r) is None
