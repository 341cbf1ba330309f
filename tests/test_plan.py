"""The lazy query-plan API: builder IR, optimizer passes, explain, limit
pushdown, and every verb against NumPy.

The tentpole claim: every query verb lowers through ONE logical plan,
ONE optimizer, and ONE streaming executor — so these tests pin (a) the
builder -> IR mapping, (b) each optimizer pass in isolation, (c) the
explain() rendering, (d) real end-to-end limit early-exit (fragments past
the budget are never scanned), and (e) that each verb returns what NumPy
computes from the source table across the layout x format grid.
"""

import numpy as np
import pytest

from repro.aformat.aggregate import AggSpec
from repro.aformat.expressions import field
from repro.core import (
    dataset,
    make_cluster,
    write_flat,
    write_split,
    write_striped,
)
from repro.dataset import (
    Aggregate,
    Count,
    Filter,
    Limit,
    Project,
    Scan,
)
from repro.dataset.plan import (
    prune_fragments,
    pushdown_limit,
    pushdown_projection,
    rewrite_count,
    rewrite_metadata_aggregate,
    _decompose,
)

WRITERS = {
    "flat": write_flat,
    "striped": write_striped,
    "split": write_split,
}
FORMATS = ["parquet", "pushdown", "adaptive"]


@pytest.fixture(params=["flat", "striped", "split"])
def populated(request, taxi_table):
    fs = make_cluster(8)
    for i in range(4):
        part = taxi_table.slice(i * 5000, 5000)
        WRITERS[request.param](
            fs, f"/d/part{i}.arw", part, row_group_rows=1024
        )
    return fs, taxi_table, request.param


@pytest.fixture
def flat_ds(taxi_table):
    fs = make_cluster(8)
    for i in range(4):
        write_flat(
            fs,
            f"/d/part{i}.arw",
            taxi_table.slice(i * 5000, 5000),
            row_group_rows=1024,
        )
    return fs, dataset(fs, "/d"), taxi_table


# ---------------------------------------------------------------------------
# builder -> IR structure
# ---------------------------------------------------------------------------


def test_builder_constructs_nested_ir(flat_ds):
    fs, ds, _ = flat_ds
    pred = field("fare_amount") > 25.0
    q = ds.query().filter(pred).select("trip_id").limit(10)
    root = q.logical_plan()
    assert isinstance(root, Limit) and root.n == 10
    proj = root.input
    assert isinstance(proj, Project) and proj.columns == ("trip_id",)
    filt = proj.input
    assert isinstance(filt, Filter) and filt.predicate is pred
    assert isinstance(filt.input, Scan) and filt.input.dataset is ds


def test_builder_aggregate_and_count_nodes(flat_ds):
    fs, ds, _ = flat_ds
    q = ds.query().aggregate(["count"], group_by="passenger_count")
    root = q.logical_plan()
    assert isinstance(root, Aggregate)
    assert root.group_by == "passenger_count"
    assert root.specs == (AggSpec("count"),)
    c = ds.query().count().logical_plan()
    assert isinstance(c, Count) and isinstance(c.input, Scan)


def test_builder_is_lazy_and_immutable(flat_ds):
    """Builder verbs derive new queries and never touch storage."""
    fs, ds, _ = flat_ds
    calls = sum(o.stats.cls_calls for o in fs.store.osds)
    base = ds.query()
    derived = base.filter(field("trip_id") < 10).select("trip_id").limit(3)
    assert isinstance(base.logical_plan(), Scan)  # base untouched
    assert isinstance(derived.logical_plan(), Limit)
    assert sum(o.stats.cls_calls for o in fs.store.osds) == calls


def test_builder_validation(flat_ds):
    fs, ds, _ = flat_ds
    with pytest.raises(KeyError):
        ds.query().select("no_such_column")
    with pytest.raises(ValueError):
        ds.query().limit(0)
    with pytest.raises(TypeError):
        ds.query().filter("not an expr")
    agg = ds.query().aggregate(["count"])
    with pytest.raises(ValueError):
        agg.filter(field("trip_id") > 0)
    with pytest.raises(ValueError):
        agg.select("trip_id")
    with pytest.raises(ValueError):
        ds.query().count().aggregate(["count"])
    # aggregating "any n rows" is refused rather than silently answered
    # over the whole input
    with pytest.raises(ValueError, match="limit"):
        ds.query().limit(10).count()
    with pytest.raises(ValueError, match="limit"):
        ds.query().limit(10).aggregate(["count"])
    # limit ON TOP of an aggregate (trim the finalized group rows) is fine
    g = (
        ds.query()
        .aggregate(["count"], group_by="passenger_count")
        .limit(2)
        .to_table()
    )
    assert len(g) == 2


def test_scanner_format_typo_raises_valueerror(flat_ds):
    fs, ds, _ = flat_ds
    with pytest.raises(ValueError, match="parquet"):
        ds.query(format="typo")
    with pytest.raises(ValueError, match="adaptive"):
        ds.query(format=42)


# ---------------------------------------------------------------------------
# optimizer passes in isolation
# ---------------------------------------------------------------------------


def test_pass_rewrite_count(flat_ds):
    fs, ds, _ = flat_ds
    root = rewrite_count(Count(Scan(ds)))
    assert isinstance(root, Aggregate)
    assert root.specs == (AggSpec("count"),)
    assert root.group_by is None
    # nested under a limit too
    root = rewrite_count(Limit(Count(Scan(ds)), 5))
    assert isinstance(root, Limit) and isinstance(root.input, Aggregate)


def test_pass_projection_pushdown(flat_ds):
    fs, ds, _ = flat_ds
    spec = _decompose(
        Project(Filter(Scan(ds), field("fare_amount") > 1.0), ("trip_id",))
    )
    cols, _ = pushdown_projection(spec, ds.schema)
    assert cols == ("trip_id",)
    # aggregates narrow to exactly the referenced columns (schema order)
    spec = _decompose(
        Aggregate(
            Scan(ds), (AggSpec("sum", "fare_amount"),), "passenger_count"
        )
    )
    cols, _ = pushdown_projection(spec, ds.schema)
    assert cols == ("passenger_count", "fare_amount")


def test_pass_prune_fragments(flat_ds):
    fs, ds, _ = flat_ds
    frags = ds.fragments()
    # trip_id is monotone: < 1024 keeps exactly the first row group
    kept, pruned = prune_fragments(frags, field("trip_id") < 1024)
    assert len(pruned) == len(frags) - 1
    assert len(kept) == 1
    # the survivor's stats prove ALL, so its residual predicate is gone
    assert kept[0][1] is None
    # predicate-free: nothing pruned, nothing rewritten
    kept, pruned = prune_fragments(frags, None)
    assert len(kept) == len(frags) and not pruned


def test_pass_metadata_rewrite(flat_ds):
    fs, ds, tbl = flat_ds
    frags = ds.fragments()
    survivors = [(f, None) for f in frags]
    # count + integer min/max are provable from footer stats: no tasks
    specs = [AggSpec("count"), AggSpec("min", "trip_id")]
    remaining, state, dec = rewrite_metadata_aggregate(
        survivors, specs, None, ds.schema
    )
    assert not remaining and len(dec) == len(frags)
    assert state.cells == [len(tbl), 0]
    # float min is NOT provable (stats skip non-finite): all fragments stay
    specs = [AggSpec("min", "fare_amount")]
    remaining, state, dec = rewrite_metadata_aggregate(
        survivors, specs, None, ds.schema
    )
    assert len(remaining) == len(frags) and not dec
    # grouped aggregates never rewrite (stats carry no per-key split)
    remaining, _, dec = rewrite_metadata_aggregate(
        survivors, [AggSpec("count")], "passenger_count", ds.schema
    )
    assert len(remaining) == len(frags) and not dec


def test_pass_limit_truncation(flat_ds):
    fs, ds, _ = flat_ds
    frags = ds.fragments()  # 1024 rows each
    survivors = [(f, None) for f in frags]
    kept, dropped, budget = pushdown_limit(survivors, 10)
    assert budget == 10
    assert len(kept) == 1  # first fragment alone guarantees 10 rows
    assert len(dropped) == len(frags) - 1
    # fragments with residual predicates guarantee nothing: all kept
    pred = field("fare_amount") > 1.0
    kept, dropped, _ = pushdown_limit([(f, pred) for f in frags], 10)
    assert len(kept) == len(frags) and not dropped
    # no limit: pass is a no-op
    kept, dropped, budget = pushdown_limit(survivors, None)
    assert len(kept) == len(frags) and budget is None


# ---------------------------------------------------------------------------
# explain(): golden output
# ---------------------------------------------------------------------------


def test_explain_golden():
    rng = np.random.default_rng(7)
    from repro.aformat.table import Table

    tbl = Table.from_pydict(
        {
            "trip_id": np.arange(4096, dtype=np.int64),
            "fare_amount": rng.gamma(2.0, 7.5, 4096).astype(np.float64),
        }
    )
    fs = make_cluster(4)
    write_flat(fs, "/g/a.arw", tbl, row_group_rows=2048)
    ds = dataset(fs, "/g")
    q = (
        ds.query(format="pushdown")
        .filter(field("trip_id") < 100)
        .select("trip_id")
        .limit(10)
    )
    golden = """\
== logical plan ==
Limit[n=10]
  Project[trip_id]
    Filter[trip_id < 100]
      Scan[flat, fragments=2, rows=4096, columns=*]
== optimizer ==
- projection-pushdown: scan ships [trip_id]
- stats-pruning: 1 of 2 fragments pruned (0 by bloom index), 0 predicate-free after ALL verdicts
- limit-pushdown: row budget 10; plan truncated to 1 tasks (0 dropped), budget rides into scan_op
== physical plan ==
executor: streaming, format=pushdown, max_inflight=16, queue_depth=4/OSD, row_budget=10
fragments: 2 total, 1 pruned, 0 metadata-answered, 1 tasks
  [0] scan /g/a.arw#0 rows=2048 pred=trip_id < 100 limit<=10 | placement=osd
  [-] pruned /g/a.arw#0 (stats prove NONE)"""
    assert q.explain() == golden


def test_explain_shows_adaptive_placement(flat_ds):
    fs, ds, _ = flat_ds
    text = (
        ds.query(format="adaptive")
        .filter(field("fare_amount") > 25.0)
        .explain()
    )
    assert "placement=" in text and "est_osd=" in text
    assert "cached=no" in text


def test_explain_cache_probe_matches_executor_keys(flat_ds):
    """The explain() cache probe must mirror the keys the executor
    actually caches under — scans, aggregates, and the degenerate-count
    rowcount path alike."""
    from repro.core import AdaptiveFormat

    fs, ds, _ = flat_ds
    fmt = AdaptiveFormat()
    pred = field("fare_amount") > 25.0
    # count: cached under the rowcount sentinel key
    ds.query(format=fmt).filter(pred).count().to_scalar()
    text = ds.query(format=fmt).filter(pred).count().explain()
    assert "cached=yes" in text and "cached=no" not in text
    # grouped aggregate: cached under the agg spec key
    agg = ["count", ("mean", "fare_amount")]
    ds.query(format=fmt).aggregate(agg, group_by="passenger_count").to_table()
    text = (
        ds.query(format=fmt)
        .aggregate(agg, group_by="passenger_count")
        .explain()
    )
    assert "cached=yes" in text and "cached=no" not in text
    # scan: cached under the (columns, predicate, limit) key
    ds.query(format=fmt).filter(pred).select("trip_id").to_table()
    text = ds.query(format=fmt).filter(pred).select("trip_id").explain()
    assert "cached=yes" in text and "cached=no" not in text


# ---------------------------------------------------------------------------
# limit pushdown end-to-end: fragments past the budget are never scanned
# ---------------------------------------------------------------------------


def test_limit_plan_truncation_skips_fragments(flat_ds):
    fs, ds, tbl = flat_ds
    q = ds.query(format="pushdown").select("trip_id").limit(10)
    out = q.to_table()
    assert len(out) == 10
    # 20 fragments exist; the plan issued exactly one task
    assert q.metrics.fragments_total == 20
    assert len(q.metrics.tasks) == 1


@pytest.mark.parametrize("fmt", FORMATS)
def test_limit_early_exit_with_predicate(flat_ds, fmt):
    """A predicate the stats cannot prove forces runtime execution — the
    executor must stop issuing fragments once the row budget is met."""
    fs, ds, tbl = flat_ds
    pred = field("fare_amount") > 1.0  # ~everything matches, not provable
    q = (
        ds.query(format=fmt, num_threads=2)
        .filter(pred)
        .select("trip_id")
        .limit(50)
    )
    out = q.to_table()
    assert len(out) == 50
    mask = tbl.column("fare_amount").values > 1.0
    valid = set(tbl.column("trip_id").values[mask].tolist())
    assert set(out.column("trip_id").values.tolist()) <= valid
    # early exit: far fewer task records than fragments
    assert len(q.metrics.tasks) < q.metrics.fragments_total


def test_limit_rides_into_scan_op(flat_ds):
    """Storage nodes honour the budget: a limited pushdown scan ships at
    most `limit` rows per task (the node slices before IPC)."""
    fs, ds, tbl = flat_ds
    pred = field("fare_amount") > 1.0
    q = ds.query(format="pushdown").filter(pred).select("trip_id").limit(5)
    q.to_table()
    assert all(t.rows_out <= 5 for t in q.metrics.tasks)
    full = ds.query(format="pushdown").select("trip_id")
    full.to_table()
    limited_wire = max(t.wire_bytes for t in q.metrics.tasks)
    full_wire = max(t.wire_bytes for t in full.metrics.tasks)
    assert limited_wire < full_wire


def test_limit_streams_through_to_batches(flat_ds):
    fs, ds, _ = flat_ds
    q = ds.query(format="pushdown").select("trip_id").limit(1500)
    batches = list(q.to_batches())
    assert sum(len(b) for b in batches) == 1500


# ---------------------------------------------------------------------------
# every verb == NumPy over the source table
# ---------------------------------------------------------------------------


def _sorted_ids(table):
    return np.sort(table.column("trip_id").values)


@pytest.mark.parametrize("fmt", FORMATS)
def test_wrapper_equivalence_to_table(populated, fmt):
    fs, tbl, _ = populated
    ds = dataset(fs, "/d")
    pred = field("fare_amount") > 25.0
    out = ds.query(format=fmt).filter(pred).select("trip_id").to_table()
    assert out.schema.names == ["trip_id"]
    mask = tbl.column("fare_amount").values > 25.0
    assert np.array_equal(
        _sorted_ids(out),
        np.sort(tbl.column("trip_id").values[mask]),
    )


@pytest.mark.parametrize("fmt", FORMATS)
def test_wrapper_equivalence_to_batches(populated, fmt):
    from repro.aformat.table import Table

    fs, tbl, _ = populated
    ds = dataset(fs, "/d")
    pred = field("fare_amount") > 30.0
    q = ds.query(format=fmt).filter(pred).select("trip_id")
    streamed = Table.concat(list(q.to_batches()))
    materialized = q.to_table()
    assert np.array_equal(_sorted_ids(streamed), _sorted_ids(materialized))
    mask = tbl.column("fare_amount").values > 30.0
    assert len(streamed) == int(mask.sum())


@pytest.mark.parametrize("fmt", FORMATS)
def test_wrapper_equivalence_aggregate(populated, fmt):
    fs, tbl, _ = populated
    ds = dataset(fs, "/d")
    pred = field("fare_amount") > 25.0
    aggs = ["count", ("sum", "fare_amount"), ("mean", "fare_amount")]
    out = (
        ds.query(format=fmt)
        .filter(pred)
        .aggregate(aggs, group_by="passenger_count")
        .to_table()
    )
    assert out.schema.names == [
        "passenger_count",
        "count",
        "sum_fare_amount",
        "mean_fare_amount",
    ]
    order = np.argsort(out.column("passenger_count").values)
    fare = tbl.column("fare_amount").values
    mask = fare > 25.0
    keys, inv = np.unique(
        tbl.column("passenger_count").values[mask], return_inverse=True
    )
    count = np.bincount(inv)
    total = np.bincount(inv, weights=fare[mask])
    assert np.array_equal(out.column("passenger_count").values[order], keys)
    assert np.array_equal(out.column("count").values[order], count)
    assert np.allclose(
        out.column("sum_fare_amount").values[order], total, rtol=1e-12
    )
    assert np.allclose(
        out.column("mean_fare_amount").values[order],
        total / count,
        rtol=1e-12,
    )


@pytest.mark.parametrize("fmt", FORMATS)
def test_wrapper_equivalence_count(populated, fmt):
    fs, tbl, _ = populated
    ds = dataset(fs, "/d")
    pred = field("fare_amount") > 25.0
    exp = int((tbl.column("fare_amount").values > 25.0).sum())
    assert ds.query(format=fmt).filter(pred).count().to_scalar() == exp
    assert ds.query(format=fmt).count().to_scalar() == len(tbl)


# ---------------------------------------------------------------------------
# metrics: per-execution snapshots, uniform wall/fragment accounting
# ---------------------------------------------------------------------------


def test_scanner_metrics_do_not_accumulate_across_runs(flat_ds):
    """Regression: a second run of the same query used to double-count
    rows / pruned fragments / tasks into one ScanMetrics."""
    fs, ds, tbl = flat_ds
    pred = field("trip_id") < 1024
    q = ds.query(format="pushdown").filter(pred)
    q.to_table()
    first = q.metrics
    n_tasks, n_pruned, n_rows = (
        len(first.tasks),
        first.fragments_pruned,
        first.rows,
    )
    q.to_table()
    assert len(q.metrics.tasks) == n_tasks
    assert q.metrics.fragments_pruned == n_pruned
    assert q.metrics.rows == n_rows
    # the first run's record is a frozen snapshot, not a shared object
    assert q.metrics is not first


def test_aggregate_metrics_do_not_accumulate(flat_ds):
    fs, ds, _ = flat_ds
    q = ds.query(format="pushdown").aggregate(["count", ("min", "trip_id")])
    q.to_table()
    first_rows = q.metrics.rows
    q.to_table()
    assert q.metrics.rows == first_rows


@pytest.mark.parametrize("fmt", FORMATS)
def test_count_rows_records_wall_and_fragments(flat_ds, fmt):
    """Regression: static-pushdown count never set wall_s; the adaptive
    count never set fragments_total.  The unified executor records both
    for every verb."""
    fs, ds, tbl = flat_ds
    q = ds.query(format=fmt).filter(field("fare_amount") > 25.0).count()
    q.to_scalar()
    assert q.metrics.fragments_total == len(ds.fragments())
    assert q.metrics.wall_s > 0
    assert q.metrics.admission != {}


def test_metadata_count_records_fragments_without_tasks(flat_ds):
    fs, ds, tbl = flat_ds
    q = ds.query(format="pushdown").count()
    assert q.to_scalar() == len(tbl)
    assert not q.metrics.tasks
    assert q.metrics.fragments_total == len(ds.fragments())
    assert q.metrics.metadata_answers == len(ds.fragments())
