"""Aggregate pushdown: Query.aggregate correctness across every
(layout x format x predicate) cell, vs a NumPy reference on the
materialized table.

The contract mirrors the paper's placement-equivalence claim, extended to
aggregation: switching where the partial aggregate runs (client decode,
storage-side ``agg_op``, or the adaptive scheduler's per-fragment choice)
never changes the result — while the pushdown placements ship partial
states of a few dozen bytes instead of materialized columns.  Exactness:
count/min/max and integer sum/mean are bit-exact under any merge order
(integer partials are exact Python ints); float sums/means are compared to
1e-9 relative (float addition is order-sensitive in the last ulp, as in
any parallel aggregation engine).
"""

import json

import numpy as np
import pytest

from repro.aformat.aggregate import (AggSpec, AggState, CardinalityError,
                                     parse_aggs, partial_aggregate)
from repro.aformat.expressions import field
from repro.aformat.schema import Field, Schema
from repro.aformat.table import Column, Table
from repro.core import (AdaptiveFormat, dataset, make_cluster, write_flat,
                        write_split, write_striped)

WRITERS = {"flat": write_flat, "striped": write_striped,
           "split": write_split}
FORMATS = ["parquet", "pushdown", "adaptive"]

AGGS = ["count", ("count", "fare_amount"), ("sum", "trip_id"),
        ("sum", "fare_amount"), ("mean", "fare_amount"),
        ("min", "trip_distance"), ("max", "fare_amount"),
        ("min", "payment_type")]

PREDICATES = {
    "none": None,
    "selective": field("fare_amount") > 25.0,
    "pruning": field("trip_id") < 3000,          # monotone: prunes groups
    "compound": (field("fare_amount") > 20.0)
    & (field("passenger_count") >= 4),
}


@pytest.fixture(params=["flat", "striped", "split"])
def populated(request, taxi_table):
    fs = make_cluster(8)
    for i in range(4):
        part = taxi_table.slice(i * 5000, 5000)
        WRITERS[request.param](fs, f"/d/part{i}.arw", part,
                               row_group_rows=1024)
    return fs, taxi_table, request.param


def _filtered(ds, fmt, name):
    """A 4-thread query over ``ds`` filtered by ``PREDICATES[name]``."""
    q = ds.query(format=fmt, num_threads=4)
    pred = PREDICATES[name]
    return q if pred is None else q.filter(pred)


def _mask(tbl, name):
    pred = PREDICATES[name]
    if pred is None:
        return np.ones(len(tbl), "?")
    cols = {f.name: tbl.column(f.name).values for f in tbl.schema}
    if name == "selective":
        return cols["fare_amount"] > 25.0
    if name == "pruning":
        return cols["trip_id"] < 3000
    return (cols["fare_amount"] > 20.0) & (cols["passenger_count"] >= 4)


def _reference_ungrouped(tbl, mask):
    """NumPy ground truth for AGGS over the masked table."""
    fare = tbl.column("fare_amount").values[mask]
    tid = tbl.column("trip_id").values[mask]
    dist = tbl.column("trip_distance").values[mask]
    pay = tbl.column("payment_type").values[mask]
    return {
        "count": int(mask.sum()),
        "count_fare_amount": int(mask.sum()),
        "sum_trip_id": int(tid.sum()) if len(tid) else 0,
        "sum_fare_amount": float(fare.sum()),
        "mean_fare_amount": float(fare.mean()) if len(fare) else None,
        "min_trip_distance": float(dist.min()) if len(dist) else None,
        "max_fare_amount": float(fare.max()) if len(fare) else None,
        "min_payment_type": min(pay) if len(pay) else None,
    }


def _check_row(out, row, ref):
    for name, want in ref.items():
        col = out.column(name)
        got = col.values[row]
        valid = col.validity is None or bool(col.validity[row])
        if want is None:
            assert not valid, name
        elif isinstance(want, float):
            assert valid, name
            assert got == pytest.approx(want, rel=1e-9), name
        else:
            assert valid, name
            assert got == want, (name, got, want)


# ---------------------------------------------------------------------------
# the full (layout x format x predicate) grid, ungrouped and grouped
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fmt", FORMATS)
@pytest.mark.parametrize("pred_name", list(PREDICATES))
def test_ungrouped_matches_numpy(populated, fmt, pred_name):
    fs, tbl, _ = populated
    ds = dataset(fs, "/d")
    out = _filtered(ds, fmt, pred_name).aggregate(AGGS).to_table()
    assert len(out) == 1
    _check_row(out, 0, _reference_ungrouped(tbl, _mask(tbl, pred_name)))


@pytest.mark.parametrize("fmt", FORMATS)
@pytest.mark.parametrize("pred_name", ["none", "selective", "pruning"])
def test_grouped_matches_numpy(populated, fmt, pred_name):
    fs, tbl, _ = populated
    ds = dataset(fs, "/d")
    out = _filtered(ds, fmt, pred_name).aggregate(
        AGGS, group_by="passenger_count").to_table()
    mask = _mask(tbl, pred_name)
    keys = tbl.column("passenger_count").values[mask]
    uk = np.unique(keys)
    assert np.array_equal(out.column("passenger_count").values, uk)
    for gi, k in enumerate(uk):
        sub = mask & (tbl.column("passenger_count").values == k)
        _check_row(out, gi, _reference_ungrouped(tbl, sub))


@pytest.mark.parametrize("fmt", FORMATS)
def test_string_group_key(populated, fmt):
    fs, tbl, _ = populated
    ds = dataset(fs, "/d")
    out = ds.query(format=fmt, num_threads=4).aggregate(
        ["count", ("sum", "trip_id")], group_by="payment_type").to_table()
    pay = np.asarray([str(v) for v in
                      tbl.column("payment_type").values])
    uk = sorted(set(pay))
    assert list(out.column("payment_type").values) == uk
    for gi, k in enumerate(uk):
        sub = pay == k
        assert out.column("count").values[gi] == int(sub.sum())
        assert out.column("sum_trip_id").values[gi] == \
            int(tbl.column("trip_id").values[sub].sum())


# ---------------------------------------------------------------------------
# empty results: all fragments pruned / predicate matches nothing
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fmt", FORMATS)
def test_all_pruned_dataset(populated, fmt):
    fs, tbl, _ = populated
    ds = dataset(fs, "/d")
    q = ds.query(format=fmt).filter(field("fare_amount") < -5.0) \
        .aggregate(AGGS)
    out = q.to_table()
    assert q.metrics.fragments_pruned == q.metrics.fragments_total
    assert not q.metrics.tasks                   # zero I/O of any kind
    _check_row(out, 0, _reference_ungrouped(tbl, np.zeros(len(tbl), "?")))
    # grouped: no rows -> no groups
    g = ds.query(format=fmt).filter(field("fare_amount") < -5.0) \
        .aggregate(AGGS, group_by="passenger_count").to_table()
    assert len(g) == 0
    assert g.schema.names[0] == "passenger_count"


@pytest.mark.parametrize("fmt", FORMATS)
def test_empty_after_scan_not_prunable(populated, fmt):
    """Predicate the stats cannot prune but no row satisfies: fragments
    are scanned, the merged state is still the empty aggregate."""
    fs, tbl, _ = populated
    ds = dataset(fs, "/d")
    pred = (field("trip_id") > 4998) & (field("trip_id") < 4999)
    q = ds.query(format=fmt).filter(pred).aggregate(AGGS)
    out = q.to_table()
    assert q.metrics.tasks                       # something was scanned
    _check_row(out, 0, _reference_ungrouped(tbl, np.zeros(len(tbl), "?")))


# ---------------------------------------------------------------------------
# metadata-only answers and wire accounting
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fmt", FORMATS)
def test_metadata_only_aggregates_never_touch_storage(populated, fmt):
    """Ungrouped, predicate-free count/min/max over non-float columns are
    provable from footer stats: zero bytes on the wire, zero cls calls."""
    fs, tbl, _ = populated
    ds = dataset(fs, "/d")
    calls_before = sum(o.stats.cls_calls for o in fs.store.osds)
    q = ds.query(format=fmt).aggregate(
        ["count", ("min", "trip_id"), ("max", "trip_id"),
         ("max", "payment_type"), ("count", "fare_amount")])
    out = q.to_table()
    assert sum(o.stats.cls_calls for o in fs.store.osds) == calls_before
    assert all(t.wire_bytes == 0 for t in q.metrics.tasks)
    assert out.column("count").values[0] == len(tbl)
    assert out.column("min_trip_id").values[0] == 0
    assert out.column("max_trip_id").values[0] == len(tbl) - 1
    assert out.column("max_payment_type").values[0] == "disp"
    assert out.column("count_fare_amount").values[0] == len(tbl)


def test_float_minmax_not_answered_from_stats(populated):
    """Footer stats skip non-finite floats, so float min/max must decode
    real data (stats would lie for a column holding inf)."""
    fs, tbl, _ = populated
    ds = dataset(fs, "/d")
    q = ds.query(format="pushdown").aggregate([("min", "fare_amount")])
    out = q.to_table()
    assert q.metrics.tasks                       # storage was consulted
    assert out.column("min_fare_amount").values[0] == pytest.approx(
        float(tbl.column("fare_amount").values.min()), rel=1e-12)


def test_grouped_pushdown_ships_partial_states_not_columns(populated):
    """The wire-bytes claim: a grouped aggregate ships orders of
    magnitude less than materializing the same fragments."""
    fs, tbl, _ = populated
    ds = dataset(fs, "/d")
    scan = ds.query(format="pushdown").select(
        "passenger_count", "fare_amount", "trip_id")
    scan.to_table()
    scan_wire = sum(t.wire_bytes for t in scan.metrics.tasks)
    agg = ds.query(format="pushdown").aggregate(
        ["count", ("sum", "fare_amount"), ("sum", "trip_id")],
        group_by="passenger_count")
    agg.to_table()
    agg_wire = sum(t.wire_bytes for t in agg.metrics.tasks)
    assert agg_wire * 20 < scan_wire             # >20x reduction


# ---------------------------------------------------------------------------
# tier-1 acceptance: striped layout, adaptive format, grouped — exact
# match at <5% of the to_table wire bytes
# ---------------------------------------------------------------------------


def test_striped_adaptive_grouped_exact_and_under_5pct_wire(taxi_table):
    fs = make_cluster(8)
    for i in range(4):
        write_striped(fs, f"/d/part{i}.arw", taxi_table.slice(i * 5000,
                                                              5000),
                      row_group_rows=1024)
    ds = dataset(fs, "/d")
    tbl = taxi_table
    pred = field("fare_amount") > 20.0
    mask = tbl.column("fare_amount").values > 20.0

    fmt = AdaptiveFormat()
    full = ds.query(format=fmt, num_threads=4).filter(pred)
    full.to_table()
    table_wire = sum(t.wire_bytes for t in full.metrics.tasks)

    q = ds.query(format=AdaptiveFormat(), num_threads=4).filter(pred) \
        .aggregate(["count", ("sum", "trip_id"),
                    ("mean", "passenger_count"),
                    ("min", "trip_id"), ("max", "trip_id")],
                   group_by="passenger_count")
    out = q.to_table()
    agg_wire = sum(t.wire_bytes for t in q.metrics.tasks)

    # exact NumPy reference (integer partials are exact in any order)
    keys = tbl.column("passenger_count").values[mask]
    tid = tbl.column("trip_id").values[mask]
    uk = np.unique(keys)
    assert np.array_equal(out.column("passenger_count").values, uk)
    for gi, k in enumerate(uk):
        m = keys == k
        assert out.column("count").values[gi] == int(m.sum())
        assert out.column("sum_trip_id").values[gi] == int(tid[m].sum())
        assert out.column("min_trip_id").values[gi] == int(tid[m].min())
        assert out.column("max_trip_id").values[gi] == int(tid[m].max())
        # mean over an int key column: exact int sum / exact count
        assert out.column("mean_passenger_count").values[gi] == \
            int(m.sum()) * float(k) / int(m.sum())
    assert agg_wire > 0
    assert agg_wire < 0.05 * table_wire, (agg_wire, table_wire)


# ---------------------------------------------------------------------------
# spill-to-scan: storage-side group-cardinality bound
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fmt", ["pushdown", "adaptive"])
def test_cardinality_spill_falls_back_to_scan(populated, fmt):
    """group-by over a unique key exceeds the storage bound: every
    fragment spills to a scan, the client folds unbounded — the result
    must still be complete and exact."""
    fs, tbl, _ = populated
    ds = dataset(fs, "/d")
    fmt_obj = AdaptiveFormat() if fmt == "adaptive" else fmt
    q = ds.query(format=fmt_obj, num_threads=4).aggregate(
        [("count", None)], group_by="trip_id", max_groups=64)
    out = q.to_table()
    assert len(out) == len(tbl)                  # every trip_id distinct
    assert np.array_equal(out.column("trip_id").values,
                          np.arange(len(tbl), dtype=np.int64))
    assert np.all(out.column("count").values == 1)
    # the spill path ran client-side folds, not agg_op replies
    assert any(t.where == "client" or t.wire_bytes > 1000
               for t in q.metrics.tasks)
    if fmt == "adaptive":
        stats = fmt_obj.stats()
        assert stats["spills"] > 0
        # spills book their final placement once, never twice
        assert sum(stats["decisions"].values()) == len(q.metrics.tasks)


# ---------------------------------------------------------------------------
# adaptive placement behaviours specific to aggregates
# ---------------------------------------------------------------------------


def test_adaptive_aggregate_result_cached(taxi_table):
    fs = make_cluster(8)
    for i in range(4):
        write_flat(fs, f"/d/part{i}.arw", taxi_table.slice(i * 5000, 5000),
                   row_group_rows=1024)
    ds = dataset(fs, "/d")
    fmt = AdaptiveFormat()
    pred = field("fare_amount") > 25.0
    aggs = [("sum", "fare_amount"), ("count", None)]
    def query():
        return ds.query(format=fmt, num_threads=4).filter(pred) \
            .aggregate(aggs, group_by="passenger_count")

    first = query()
    a = first.to_table()
    second = query()
    b = second.to_table()
    assert second.metrics.cache_hits == len(second.metrics.tasks)
    assert all(t.wire_bytes == 0 for t in second.metrics.tasks)
    assert a.equals(b)
    # an overwrite bumps the version: fragments of that object miss
    name = fs.object_names("/d/part0.arw")[0]
    fs.store.put(name, fs.store.get(name))
    third = query()
    c = third.to_table()
    assert 0 < third.metrics.cache_hits < len(third.metrics.tasks)
    assert a.equals(c)


def test_adaptive_aggregate_saturation_goes_client_side(taxi_table):
    fs = make_cluster(8)
    for i in range(4):
        write_flat(fs, f"/d/part{i}.arw", taxi_table.slice(i * 5000, 5000),
                   row_group_rows=1024)
    for osd in fs.store.osds:
        osd.background_load = 64 * osd.threads
    ds = dataset(fs, "/d")
    fmt = AdaptiveFormat()
    out = ds.query(format=fmt, num_threads=4).filter(
        field("fare_amount") > 25.0).aggregate(
        [("count", None)], group_by="passenger_count").to_table()
    dec = fmt.stats()["decisions"]
    assert dec["osd"] == 0 and dec["client"] > 0
    exp = (taxi_table.column("fare_amount").values > 25.0)
    assert int(out.column("count").values.sum()) == int(exp.sum())


def test_aggregate_survives_osd_failure(populated):
    fs, tbl, _ = populated
    ds = dataset(fs, "/d")
    fs.store.fail_osd(fs.store.osds[0].osd_id)
    fs.store.fail_osd(fs.store.osds[3].osd_id)
    out = ds.query(format="adaptive", num_threads=4).aggregate(
        [("sum", "trip_id"), ("count", None)]).to_table()
    n = len(tbl)
    assert out.column("count").values[0] == n
    assert out.column("sum_trip_id").values[0] == n * (n - 1) // 2


# ---------------------------------------------------------------------------
# nullable columns
# ---------------------------------------------------------------------------


def test_aggregate_nullable_column():
    fs = make_cluster(4)
    n = 4000
    rng = np.random.default_rng(7)
    valid = rng.random(n) > 0.3
    vals = rng.integers(0, 100, n).astype(np.int64)
    keys = rng.integers(0, 5, n).astype(np.int32)
    sch = Schema((Field("k", "int32"), Field("v", "int64", nullable=True)))
    tbl = Table(sch, [Column(sch.fields[0], keys),
                      Column(sch.fields[1], vals, valid.copy())])
    write_flat(fs, "/n/part0.arw", tbl, row_group_rows=512)
    ds = dataset(fs, "/n")
    for fmt in FORMATS:
        out = ds.query(format=fmt).aggregate(
            ["count", ("count", "v"), ("sum", "v"), ("mean", "v")],
            group_by="k").to_table()
        for gi, k in enumerate(np.unique(keys)):
            m = keys == k
            mv = m & valid
            assert out.column("count").values[gi] == int(m.sum())
            assert out.column("count_v").values[gi] == int(mv.sum())
            assert out.column("sum_v").values[gi] == int(vals[mv].sum())
            assert out.column("mean_v").values[gi] == pytest.approx(
                vals[mv].mean())


# ---------------------------------------------------------------------------
# spec validation and the partial-state unit contract
# ---------------------------------------------------------------------------


def test_aggspec_validation():
    with pytest.raises(ValueError):
        AggSpec("median", "x")
    with pytest.raises(ValueError):
        AggSpec("sum")                           # sum needs a column
    assert parse_aggs(["count", "sum(x)", ("min", "y"),
                       AggSpec("max", "z")]) == [
        AggSpec("count"), AggSpec("sum", "x"), AggSpec("min", "y"),
        AggSpec("max", "z")]
    assert parse_aggs(["count(*)"]) == [AggSpec("count")]


def test_sum_over_string_raises(populated):
    fs, tbl, _ = populated
    ds = dataset(fs, "/d")
    with pytest.raises(TypeError):
        ds.query(format="parquet").aggregate(
            [("sum", "payment_type")]).to_table()


def test_unknown_column_raises(populated):
    fs, tbl, _ = populated
    ds = dataset(fs, "/d")
    with pytest.raises(KeyError):
        ds.query(format="pushdown").aggregate([("sum", "nope")]).to_table()
    with pytest.raises(KeyError):
        ds.query(format="pushdown").aggregate(
            ["count"], group_by="nope").to_table()


def test_partial_state_roundtrip_and_merge_associativity():
    rng = np.random.default_rng(0)
    tbl = Table.from_pydict({
        "k": rng.integers(0, 4, 300).astype(np.int32),
        "x": rng.integers(-50, 50, 300).astype(np.int64),
    })
    specs = parse_aggs(["count", ("sum", "x"), ("mean", "x"),
                        ("min", "x"), ("max", "x")])
    thirds = [tbl.slice(0, 100), tbl.slice(100, 100), tbl.slice(200, 100)]
    parts = [partial_aggregate(t, specs, "k") for t in thirds]
    ab_c = AggState.empty(specs, "k")
    ab_c.merge(parts[0]).merge(parts[1]).merge(parts[2])
    c_ba = AggState.empty(specs, "k")
    c_ba.merge(parts[2]).merge(parts[1]).merge(parts[0])
    assert ab_c.groups == c_ba.groups            # int partials: exact
    rt = AggState.deserialize(ab_c.serialize())
    assert rt.groups == ab_c.groups and rt.rows == ab_c.rows
    # the wire form is compact JSON, not columns
    assert len(ab_c.serialize()) < 1024
    assert json.loads(ab_c.serialize())["group_by"] == "k"


def test_cardinality_error_is_storage_side_only():
    tbl = Table.from_pydict({"k": np.arange(100, dtype=np.int64)})
    with pytest.raises(CardinalityError):
        partial_aggregate(tbl, parse_aggs(["count"]), "k", max_groups=10)
    # unbounded (client) path: fine
    st = partial_aggregate(tbl, parse_aggs(["count"]), "k")
    assert st.num_groups == 100


# ---------------------------------------------------------------------------
# the serving planner's sizing query (serve-layer integration)
# ---------------------------------------------------------------------------


def test_prompt_lengths_ships_counts_not_tokens():
    from repro.serve.engine import prompt_lengths
    fs = make_cluster(4)
    rng = np.random.default_rng(3)
    uids = np.repeat(np.arange(16, dtype=np.int64), 8)
    pos = np.tile(np.arange(8, dtype=np.int32), 16)
    toks = rng.integers(0, 1000, uids.size).astype(np.int32)
    tbl = Table.from_pydict({"uid": uids, "pos": pos, "token": toks})
    write_flat(fs, "/prompts/p0.arw", tbl, row_group_rows=32)
    ds = dataset(fs, "/prompts")
    lengths, metrics = prompt_lengths(ds)
    assert lengths == {u: 8 for u in range(16)}
    # counts on the wire, not token columns
    token_bytes = tbl.select(["token"]).nbytes()
    assert sum(t.wire_bytes for t in metrics.tasks) < token_bytes
