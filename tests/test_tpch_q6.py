"""Dates, decimals and a computed measure, end to end: TPC-H Q6 over a
seeded ~20k-row LINEITEM through ``Dataset.query``, byte-identical to the
plain reference under client NumPy, client Pallas and the OSD's
``agg_op``, with the literals exact and the sums exact."""

import datetime
import sys
from decimal import Decimal
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from perfbench import harness, tpch  # noqa: E402
from perfbench.drivers.tpch_agg import q6_filter, q6_measure  # noqa: E402
from perfbench.recorder import RecordingBackend  # noqa: E402
from perfbench.references import tpch_q6 as reference  # noqa: E402
from repro.aformat import encodings, parquet  # noqa: E402
from repro.aformat.aggregate import (AggSpec, AggState,  # noqa: E402
                                     partial_aggregate, result_fields)
from repro.aformat.decode import PallasBackend  # noqa: E402
from repro.aformat.expressions import ALL, SOME, Expr, field  # noqa: E402
from repro.aformat.schema import (Field, Schema, decimal64,  # noqa: E402
                                  schema, to_physical)
from repro.aformat.statistics import ColumnStats  # noqa: E402
from repro.aformat.table import Table  # noqa: E402
from repro.core import dataset, make_cluster, write_flat  # noqa: E402

CONFIG = harness.load_json(ROOT / "perfbench" / "configs" /
                           "tpch_lineitem.json")
PARAMS = {"date": "1994-01-01", "discount": "0.06", "quantity": 24}
DEC = decimal64(15, 2)
SEED = 2**33 + 15


def lineitem_objects(seed=SEED, orders=5000, rows_per_object=8000):
    """The generator's objects at a small size: their tables and the
    reference's arrays."""
    cfg = dict(CONFIG, orders_per_sf=orders, rows_per_object=rows_per_object)
    o = tpch.orders(cfg, seed)
    pool = tpch.text_pool(seed)
    sch = Schema(tuple(Field(c["name"], c["type"]) for c in cfg["columns"]))
    out = []
    for i, span in enumerate(tpch.object_orders(o["lines"],
                                                rows_per_object)):
        cols = tpch.lineitem(cfg, o, span, seed, i, pool)
        out.append((Table.from_pydict(cols, sch), cols))
    return out


@pytest.fixture(scope="module")
def lineitem():
    objs = lineitem_objects()
    fs = make_cluster(4)
    for i, (tbl, _) in enumerate(objs):
        write_flat(fs, f"/lineitem/{i:05d}/part.arw", tbl,
                   row_group_rows=len(tbl))
    return dataset(fs, "/lineitem"), [cols for _, cols in objs]


def q6(ds, **placement):
    return ds.query(**placement).filter(q6_filter(PARAMS)).aggregate(
        [("sum", q6_measure())])


# -- types -------------------------------------------------------------------

#: values that make the writer's heuristic pick each encoding it picks
#: for an integer column
SHAPES = {
    encodings.DELTA: lambda rng: np.sort(rng.integers(8000, 8100, 4000)),
    encodings.RLE: lambda rng: np.repeat(rng.permutation(np.arange(
        8000, 8040)), 100),
    encodings.DICT: lambda rng: rng.choice([8035, 9131, 10592, -3], 4000),
    encodings.PLAIN: lambda rng: rng.integers(-10**6, 10**6, 4000),
}


@pytest.mark.parametrize("type_", ["date32", DEC, decimal64(18, 4)])
@pytest.mark.parametrize("encoding", sorted(SHAPES))
def test_dates_and_decimals_round_trip_under_each_encoding(type_, encoding):
    f = Field("x", type_)
    values = SHAPES[encoding](np.random.default_rng(5)).astype(f.numpy_dtype)
    assert encodings.choose_encoding(f.physical, values) == encoding
    tbl = Table.from_pydict({"x": values}, Schema((f,)))
    data = parquet.write_table(tbl, row_group_rows=len(values))
    src = parquet.BytesSource(data)
    meta = parquet.read_footer(src)
    assert meta.schema.field("x").type == type_
    assert meta.row_groups[0].chunks[0].encoding == encoding
    st = meta.row_groups[0].chunks[0].stats
    assert (st.min, st.max) == (values.min(), values.max())
    for backend in (None, "pallas"):
        out = parquet.scan_file(src, backend=backend).column("x")
        assert out.field.type == type_
        assert out.values.dtype == f.numpy_dtype
        assert out.values.tobytes() == values.tobytes()
    back = Table.from_ipc(tbl.to_ipc())
    assert back.schema == tbl.schema
    assert back.column("x").values.tobytes() == values.tobytes()


@pytest.mark.parametrize("type_", ["date64", "decimal64(19,2)",
                                   "decimal64(5,6)", "decimal(15,2)"])
def test_unknown_or_too_wide_types_are_refused(type_):
    with pytest.raises(ValueError):
        Field("x", type_)


def test_types_name_their_storage():
    assert Field("d", "date32").physical == "int32"
    assert Field("p", DEC).physical == "int64"
    assert Field("p", DEC).numpy_dtype == np.dtype("<i8")


# -- literals ----------------------------------------------------------------

@pytest.mark.parametrize("type_, value, stored", [
    ("date32", datetime.date(1994, 1, 1), 8766),
    ("date32", datetime.date(1970, 1, 1), 0),
    ("date32", datetime.date(1969, 12, 31), -1),
    (DEC, Decimal("0.06"), 6),
    (DEC, Decimal("0.060"), 6),
    (DEC, Decimal("24"), 2400),
    (DEC, Decimal("-1.5"), -150),
    (DEC, Decimal("9999999999999.99"), 999999999999999),
    (decimal64(18, 4), Decimal("123141078.2283"), 1231410782283),
    # a plain number is a stored value already
    (DEC, 7, 7),
    ("int64", 5, 5),
])
def test_literals_convert_exactly(type_, value, stored):
    got = to_physical(type_, value)
    assert got == stored and type(got) is type(stored)


@pytest.mark.parametrize("type_, value, error", [
    (DEC, Decimal("0.065"), ValueError),          # not exact at scale 2
    (DEC, Decimal("0.0600001"), ValueError),
    (DEC, Decimal("10000000000000"), ValueError),  # past 15 digits
    (DEC, Decimal("NaN"), ValueError),
    (DEC, datetime.date(1994, 1, 1), TypeError),
    ("int64", Decimal("1"), TypeError),
    ("int32", datetime.date(1994, 1, 1), TypeError),
    ("date32", datetime.datetime(1994, 1, 1), TypeError),
    ("date32", Decimal("1"), TypeError),
])
def test_inexact_or_mistyped_literals_raise(type_, value, error):
    with pytest.raises(error):
        to_physical(type_, value)


def test_a_query_refuses_an_inexact_literal_when_it_is_built(lineitem):
    ds, _ = lineitem
    with pytest.raises(ValueError):
        ds.query().filter(field("l_discount") <= Decimal("0.065"))
    with pytest.raises(TypeError):
        ds.query().filter(field("l_quantity") < datetime.date(1994, 1, 1))


def test_literals_evaluate_bind_and_travel_exactly():
    sch = schema(("d", "date32"), ("p", DEC))
    tbl = Table.from_pydict({"d": np.array([8765, 8766, 8767], np.int32),
                             "p": np.array([5, 6, 7])}, sch)
    pred = ((field("d") >= datetime.date(1994, 1, 1))
            & (field("p") <= Decimal("0.06")))
    want = [False, True, False]
    assert pred.evaluate(tbl).tolist() == want
    bound = pred.bind(sch)
    assert bound.lhs.value == 8766 and bound.rhs.value == 6
    assert bound.evaluate(tbl).tolist() == want
    # the bound predicate travels as plain numbers
    assert Expr.from_json(bound.to_json()) == bound
    # an unbound constant proves nothing from stored min/max
    stats = {"d": ColumnStats(9000, 9100, 0, 3)}
    assert pred.lhs.prune(stats) == SOME and bound.lhs.prune(stats) == ALL
    # a predicate with nothing to convert binds to itself
    plain = field("d") >= 8766
    assert plain.bind(sch) is plain


def test_stats_prune_a_date_range():
    rng = np.random.default_rng(2)
    n = 12_000
    days = np.sort(rng.integers(8035, 10592, n)).astype(np.int32)
    tbl = Table.from_pydict({"l_shipdate": days,
                             "l_discount": rng.integers(0, 11, n)},
                            schema(("l_shipdate", "date32"),
                                   ("l_discount", DEC)))
    fs = make_cluster(4)
    write_flat(fs, "/d/part.arw", tbl, row_group_rows=1000)
    q = dataset(fs, "/d").query(format="parquet").filter(
        (field("l_shipdate") >= datetime.date(1994, 1, 1))
        & (field("l_shipdate") < datetime.date(1995, 1, 1)))
    out = q.to_table()
    keep = (days >= 8766) & (days < 9131)
    assert out.column("l_shipdate").values.tobytes() == days[keep].tobytes()
    # 12 row groups over seven years; one year's rows lie in two or three
    assert q.metrics.fragments_total == 12
    assert q.metrics.fragments_pruned >= 9


# -- the measure and its exact sum -------------------------------------------

def test_the_product_measure_is_named_and_travels():
    m = field("l_extendedprice") * field("l_discount")
    assert m.columns() == {"l_extendedprice", "l_discount"}
    spec = AggSpec("sum", m)
    assert spec.name == reference.NAME
    assert AggSpec.from_json(spec.to_json()) == spec
    # a literal is exact: an int or a decimal, never a float
    with pytest.raises(TypeError):
        field("a") * 2.5


def test_result_types_follow_the_decimals():
    sch = schema(("p", DEC), ("d", DEC), ("day", "date32"), ("n", "int32"))
    specs = [AggSpec("sum", field("p") * field("d")), AggSpec("sum", "p"),
             AggSpec("max", "p"), AggSpec("min", "day"),
             AggSpec("mean", "p"), AggSpec("sum", field("p") * field("n"))]
    assert [f.type for f in result_fields(specs, None, sch)] == [
        "decimal64(18,4)", "decimal64(18,2)", DEC, "date32", "float64",
        "decimal64(18,2)"]
    tbl = Table.from_pydict({"p": np.array([150, 250]),
                             "d": np.array([1, 2]),
                             "day": np.array([3, 4], np.int32),
                             "n": np.array([2, 3], np.int32)}, sch)
    out = partial_aggregate(tbl, specs).finalize(sch)
    assert [c.values[0] for c in out.columns] == [650, 400, 250, 3, 2.0,
                                                  1050]
    with pytest.raises(TypeError):
        partial_aggregate(tbl, [AggSpec("sum", "day")])


def test_the_sum_of_the_product_is_exact():
    rng = np.random.default_rng(9)
    n = 10_000
    # a sum far past 2**53: a float64 sum would round it
    a = rng.integers(10**6, 10**7, n)
    b = rng.integers(10**6, 10**7, n)
    sch = schema(("a", DEC), ("b", decimal64(15, 3)), ("g", "int32"))
    tbl = Table.from_pydict({"a": a, "b": b,
                             "g": rng.integers(0, 3, n).astype(np.int32)},
                            sch)
    spec = [AggSpec("sum", field("a") * field("b"))]
    want = sum(int(x) * int(y) for x, y in zip(a, b))
    assert want != int(np.sum(a.astype(np.float64) * b))
    out = partial_aggregate(tbl, spec).finalize(sch).columns[0]
    assert out.field.type == "decimal64(18,5)"
    assert out.values.tolist() == [want]
    # split across fragments and merged, and grouped, it is the same
    halves = [partial_aggregate(tbl.slice(0, n // 2), spec),
              partial_aggregate(tbl.slice(n // 2, n - n // 2), spec)]
    merged = AggState.deserialize(halves[0].serialize()).merge(halves[1])
    assert merged.finalize(sch).columns[0].values.tolist() == [want]
    grouped = partial_aggregate(tbl, spec, group_by="g").finalize(sch)
    assert sum(grouped.columns[1].values.tolist()) == want


def test_an_overflow_raises():
    sch = schema(("a", decimal64(18, 2)), ("b", decimal64(18, 2)))
    # a product past int64
    big = Table.from_pydict({"a": np.array([10**10]),
                             "b": np.array([10**10])}, sch)
    with pytest.raises(OverflowError):
        partial_aggregate(big, [AggSpec("sum", field("a") * field("b"))])
    # products that fit, summed past 18 digits
    many = Table.from_pydict({"a": np.full(200, 10**8),
                              "b": np.full(200, 10**8)}, sch)
    state = partial_aggregate(many, [AggSpec("sum", field("a") * field("b"))])
    assert state.cells == [200 * 10**16]
    with pytest.raises(OverflowError):
        state.finalize(sch)
    # a bare decimal column summed past 18 digits
    col = Table.from_pydict({"a": np.full(20, 10**17), "b": np.zeros(20)},
                            sch)
    with pytest.raises(OverflowError):
        partial_aggregate(col, [AggSpec("sum", "a")]).finalize(sch)


# -- Q6 -----------------------------------------------------------------------

PLACEMENTS = {"client_numpy": {"format": "parquet"},
              "client_pallas": {"format": "parquet",
                                "decode_backend": "pallas"},
              "pushdown_agg_op": {"format": "pushdown"}}


@pytest.mark.parametrize("placement", sorted(PLACEMENTS))
def test_q6_is_byte_identical_to_the_reference(lineitem, placement):
    ds, objs = lineitem
    assert len(objs) >= 2 and 18_000 < sum(len(o["l_orderkey"])
                                           for o in objs) < 22_000
    out = q6(ds, **PLACEMENTS[placement]).to_table()
    want = {reference.NAME: np.asarray(
        [sum(reference.revenue(o, PARAMS) for o in objs)], np.int64)}
    got = [(c.field.name, c.field.type, c.values, c.validity)
           for c in out.columns]
    assert not reference.differs(got, want)
    assert want[reference.NAME][0] > 0
    # and one object alone, as the benchmark's tasks run it
    one = dataset(ds.fs, "/lineitem/00001")
    out = q6(one, **PLACEMENTS[placement]).to_table()
    assert out.columns[0].values.tolist() == [reference.revenue(objs[1],
                                                                PARAMS)]


def test_q6_runs_on_the_kernels_with_no_silent_fallback(lineitem):
    ds, objs = lineitem
    backend = RecordingBackend()
    backend.recording = True
    q6(ds, format="parquet", decode_backend=backend).to_table()
    assert len(backend.reports) == len(objs)
    for rep in backend.reports:
        assert rep["predicate"] == "kernel"
        assert rep["compact"] == {"l_extendedprice": "kernel",
                                  "l_discount": "kernel"}
        for name in ("l_quantity", "l_discount"):
            assert rep["columns"][name] == "kernel"


def test_explain_and_the_live_report_agree_on_dates_and_decimals():
    rng = np.random.default_rng(4)
    n = 20_000
    sch = schema(("day", "date32"), ("price", DEC), ("wide", DEC))
    tbl = Table.from_pydict({
        "day": rng.integers(8035, 10592, n).astype(np.int32),
        "price": rng.choice(rng.integers(90_000, 10_495_000, 500), n),
        # past the f32-exact domain: host on both counts
        "wide": rng.choice(np.array([2**40, 2**41, 5]), n)}, sch)
    data = parquet.write_table(tbl, row_group_rows=n)
    src = parquet.BytesSource(data)
    meta = parquet.read_footer(src)
    assert [c.encoding for c in meta.row_groups[0].chunks] == [
        encodings.DICT] * 3
    backend = PallasBackend()
    pred = field("day") >= 8766
    assert backend.describe(meta, meta.row_groups[0], None, pred) == \
        "pallas[kernel=day,price; host=wide(dict)] pred=fused"
    report = {}
    backend.scan_row_group(src, meta, meta.row_groups[0], None, pred,
                           report)
    assert report["columns"] == {"day": "kernel", "price": "kernel",
                                 "wide": "host"}
    assert report["predicate"] == "kernel"
