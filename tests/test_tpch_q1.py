"""Group-by on two string keys and exact measure arithmetic, end to end:
TPC-H Q1 over a seeded ~20k-row LINEITEM through ``Dataset.query``,
byte-identical to the plain reference under client NumPy, client Pallas
(the keys as dictionary codes, the fold on the ``group_fold`` kernel)
and the OSD's ``agg_op``; and the measure tree's exact scales, literals
and overflow."""

import sys
from decimal import Decimal
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from perfbench import harness, tpch  # noqa: E402
from perfbench.drivers.tpch_q1 import q1_aggregates, q1_filter  # noqa: E402
from perfbench.references import tpch_q1 as reference  # noqa: E402
from repro.aformat.aggregate import (AggSpec, measure_terms,  # noqa: E402
                                     measure_type, partial_aggregate,
                                     result_fields)
from repro.aformat.decode import PallasBackend  # noqa: E402
from repro.aformat.expressions import Measure, field  # noqa: E402
from repro.aformat.schema import Field, Schema, decimal64, schema  # noqa: E402
from repro.aformat.table import Table  # noqa: E402
from repro.core import dataset, make_cluster, write_flat  # noqa: E402

CONFIG = harness.load_json(ROOT / "perfbench" / "configs" /
                           "tpch_lineitem.json")
PARAMS = {"delta": 90}
DEC = decimal64(15, 2)
SEED = 2**34 + 19


class FoldReports(PallasBackend):
    """The Pallas backend, keeping every scan's and fold's report."""

    def __init__(self):
        self.reports = []

    def scan_row_group(self, src, meta, rg, columns=None, predicate=None,
                       report=None, codes=()):
        report = {} if report is None else report
        out = super().scan_row_group(src, meta, rg, columns, predicate,
                                     report, codes)
        self.reports.append(report)
        return out

    def fold(self, table, specs, group_by, report=None):
        report = {} if report is None else report
        state = super().fold(table, specs, group_by, report)
        self.reports.append(report)
        return state


@pytest.fixture(scope="module")
def lineitem():
    cfg = dict(CONFIG, orders_per_sf=5000, rows_per_object=8000)
    o = tpch.orders(cfg, SEED)
    pool = tpch.text_pool(SEED)
    sch = Schema(tuple(Field(c["name"], c["type"]) for c in cfg["columns"]))
    fs = make_cluster(4)
    cols = []
    for i, span in enumerate(tpch.object_orders(o["lines"], 8000)):
        c = tpch.lineitem(cfg, o, span, SEED, i, pool)
        write_flat(fs, f"/lineitem/{i:05d}/part.arw",
                   Table.from_pydict(c, sch), row_group_rows=8000)
        cols.append(c)
    whole = {k: np.concatenate([c[k] for c in cols])
             for k in reference.COLUMNS}
    return dataset(fs, "/lineitem"), whole


def q1(ds, **placement):
    return ds.query(**placement).filter(q1_filter(PARAMS)).aggregate(
        q1_aggregates(), group_by=reference.KEYS)


def _got(table):
    return [(c.field.name, c.field.type, c.values, c.validity)
            for c in table.columns]


PLACEMENTS = {"client_numpy": {"format": "parquet"},
              "client_pallas": {"format": "parquet",
                                "decode_backend": "pallas"},
              "pushdown_agg_op": {"format": "pushdown"}}


@pytest.mark.parametrize("placement", PLACEMENTS)
def test_q1_equals_the_reference_byte_for_byte(lineitem, placement):
    ds, cols = lineitem
    out = q1(ds, **PLACEMENTS[placement]).to_table()
    want = reference.answer(cols, PARAMS)
    assert not reference.differs(_got(out), want)
    assert len(out) == 4
    assert reference.differs(_got(out),
                             reference.control_answer(cols, PARAMS))


def test_q1_on_pallas_keeps_the_keys_as_codes_and_folds_on_the_kernel(
        lineitem):
    ds, cols = lineitem
    backend = FoldReports()
    out = q1(ds, format="parquet", decode_backend=backend).to_table()
    assert not reference.differs(_got(out), reference.answer(cols, PARAMS))
    scans = [r for r in backend.reports if "columns" in r]
    folds = [r for r in backend.reports if "fold" in r]
    assert scans and len(folds) == len(scans)
    for r in scans:
        assert r["codes"] == reference.KEYS
        assert not set(reference.KEYS) & set(r["columns"])
        assert r["compact"]["l_returnflag"] == "kernel"
    assert {r["fold"] for r in folds} == {"kernel"}
    assert {r["groups"] for r in folds} == {4}


def test_q1_result_types_and_names():
    sch = Schema(tuple(Field(c["name"], c["type"])
                       for c in CONFIG["columns"]))
    specs = [AggSpec(op, col) for op, col in q1_aggregates()]
    fields = result_fields(specs, reference.KEYS, sch)
    assert [(f.name, f.type) for f in fields] == [
        (n, t) for n, t, _ in reference.RESULT]


# -- the measure tree ---------------------------------------------------------

SCH = schema(("p", DEC), ("d", DEC), ("t", DEC), ("n", "int32"),
             ("f", "float64"), ("day", "date32"), ("s", "string"))


def _table(**cols):
    return Table.from_pydict(cols, SCH.select(list(cols)))


def test_measure_scales_are_exact_2_4_6():
    p, d, t = field("p"), field("d"), field("t")
    assert measure_type("p", SCH) == DEC
    assert measure_type(p * (1 - d), SCH) == "decimal64(18,4)"
    assert measure_type(p * (1 - d) * (1 + t), SCH) == "decimal64(18,6)"
    assert measure_type(p * field("n"), SCH) == "decimal64(18,2)"
    assert measure_type(field("n") * 2 - 1, SCH) == "int64"
    assert measure_type(p * field("f"), SCH) == "float64"
    assert (p * (1 - d) * (1 + t)).name == "p*(1-d)*(1+t)"
    assert (p - (d + t)).name == "p-(d+t)"


def test_a_literal_takes_its_operands_scale():
    p, d = field("p"), field("d")
    tbl = _table(p=np.array([1050, 2000]), d=np.array([5, 10]))
    # 1 - 0.05 is 0.95: at scale 2, 100 - 5
    state = partial_aggregate(tbl, [AggSpec("sum", 1 - d),
                                    AggSpec("sum", p * (1 - d)),
                                    AggSpec("sum", d + Decimal("0.005")),
                                    AggSpec("sum", Decimal("0.5") * p)])
    assert state.cells == [95 + 90, 1050 * 95 + 2000 * 90,
                           (50 + 5) + (100 + 5), 5 * 1050 + 5 * 2000]
    out = state.finalize(SCH)
    assert [c.field.type for c in out.columns] == [
        "decimal64(18,2)", "decimal64(18,4)", "decimal64(18,3)",
        "decimal64(18,3)"]
    assert measure_terms(p * (1 - d) * (1 + field("t")), SCH) == {
        ("p",): 10000, ("p", "t"): 100, ("d", "p"): -100,
        ("d", "p", "t"): -1}


def test_a_measure_that_might_overflow_raises():
    big = _table(p=np.array([10**12]), d=np.array([10**9]),
                 t=np.array([1]))
    p, d = field("p"), field("d")
    with pytest.raises(OverflowError):
        partial_aggregate(big, [AggSpec("sum", p * d)])
    # the alignment of a literal's scale counts too
    with pytest.raises(OverflowError):
        partial_aggregate(big, [AggSpec("sum", p * p * (1 + Decimal(
            "0.000001")))])
    assert partial_aggregate(big, [AggSpec("sum", p * (1 - field("t")))]
                             ).cells == [10**12 * 99]


def test_a_measure_refuses_what_is_not_exact():
    with pytest.raises(TypeError):
        field("p") * 0.5
    with pytest.raises(TypeError):
        measure_type(field("p") * field("day"), SCH)
    with pytest.raises(TypeError):
        measure_type(field("s") + 1, SCH)
    with pytest.raises(TypeError):
        measure_type(Measure.lit(2) * 3, SCH)


def test_the_measure_travels_as_json():
    m = field("p") * (1 - field("d")) * (1 + field("t"))
    assert Measure.from_json(m.to_json()) == m
    spec = AggSpec("sum", field("p") * Decimal("0.25"))
    assert AggSpec.from_json(spec.to_json()) == spec
    # the two-column product's form, as a Q6 payload carries it
    old = {"op": "sum", "column": {"kind": "mul",
                                   "columns": ["l_extendedprice",
                                               "l_discount"]}}
    assert AggSpec.from_json(old) == AggSpec(
        "sum", field("l_extendedprice") * field("l_discount"))


def test_the_pallas_fold_says_why_it_takes_the_host():
    rng = np.random.default_rng(1)
    tbl = _table(p=rng.integers(0, 10**6, 50), s=np.asarray(
        ["a", "b"], object)[rng.integers(0, 2, 50)])
    specs = [AggSpec("sum", "p"), AggSpec("count")]
    cases = [("s", "host:key:s"),                  # strings, not codes
             (None, "kernel")]
    for group_by, route in cases:
        report = {}
        state = PallasBackend().fold(tbl, specs, group_by, report)
        assert report["fold"] == route
        host = partial_aggregate(tbl, specs, group_by)
        assert (state.cells, state.groups) == (host.cells, host.groups)
    report = {}
    PallasBackend().fold(tbl, [AggSpec("max", "p")], None, report)
    assert report["fold"] == "host:op:max"
    wide = _table(p=np.array([2**24, 1]), s=np.array(["a", "b"], object))
    PallasBackend().fold(wide, specs, None, report)
    assert report["fold"] == "host:p:f32-domain"
