"""Dataset API: discovery, pruning, and placement-equivalence.

The paper's core claim is behavioural: switching ParquetFormat ->
PushdownParquetFormat changes *where* the scan runs, never *what* it
returns.  These tests pin that equivalence across all three layouts, plus
the pruning, queue-depth, metrics, and failover behaviour.
"""

import numpy as np
import pytest

from repro.aformat.expressions import field
from repro.core import (dataset, make_cluster, write_flat, write_split,
                        write_striped)

WRITERS = {"flat": write_flat, "striped": write_striped,
           "split": write_split}


@pytest.fixture(params=["flat", "striped", "split"])
def populated(request, taxi_table):
    fs = make_cluster(8)
    for i in range(4):
        part = taxi_table.slice(i * 5000, 5000)
        WRITERS[request.param](fs, f"/d/part{i}.arw", part,
                               row_group_rows=1024)
    return fs, taxi_table, request.param


def _expected(tbl, mask, cols):
    return tbl.filter(mask).select(cols)


def test_discovery(populated):
    fs, tbl, layout = populated
    ds = dataset(fs, "/d")
    assert ds.layout == layout
    assert ds.num_rows == len(tbl)
    assert len(ds.fragments()) == 4 * (5000 // 1024 + 1)


@pytest.mark.parametrize("fmt", ["parquet", "pushdown"])
def test_scan_equivalence(populated, fmt):
    fs, tbl, _ = populated
    ds = dataset(fs, "/d")
    pred = (field("fare_amount") > 25.0) & (field("passenger_count") >= 4)
    mask = ((tbl.column("fare_amount").values > 25.0)
            & (tbl.column("passenger_count").values >= 4))
    out = ds.query(format=fmt, num_threads=4).filter(pred).select(
        "trip_id", "fare_amount").to_table()
    exp = _expected(tbl, mask, ["trip_id", "fare_amount"])
    # row order may differ across parallel scans: sort by key
    o = np.argsort(out.column("trip_id").values)
    e = np.argsort(exp.column("trip_id").values)
    assert np.array_equal(out.column("trip_id").values[o],
                          exp.column("trip_id").values[e])
    assert np.allclose(out.column("fare_amount").values[o],
                       exp.column("fare_amount").values[e])


def test_both_placements_agree(populated):
    fs, tbl, _ = populated
    ds = dataset(fs, "/d")
    pred = field("payment_type") == "cash"
    a = ds.query(format="parquet", num_threads=2).filter(pred).to_table()
    b = ds.query(format="pushdown", num_threads=2).filter(pred).to_table()
    ka = np.sort(a.column("trip_id").values)
    kb = np.sort(b.column("trip_id").values)
    assert np.array_equal(ka, kb)


def test_pruning_skips_row_groups(populated):
    fs, tbl, _ = populated
    ds = dataset(fs, "/d")
    # trip_id is monotonically increasing: a range predicate must prune
    pred = field("trip_id") < 1024
    q = ds.query(format="pushdown").filter(pred)
    out = q.to_table()
    assert len(out) == 1024
    assert q.metrics.fragments_pruned > 0
    assert q.metrics.fragments_pruned + len(q.metrics.tasks) == \
        q.metrics.fragments_total


def test_pushdown_moves_cpu_to_storage(populated):
    # numeric projection: the paper's workload (their taxi table is numeric;
    # our simulated IPC string decode is a Python loop, which would blur the
    # client-idle claim that real zero-copy Arrow IPC provides).
    # min-of-3: wall-clock-derived CPU accounting is noisy on a loaded
    # 1-core CI host.
    cols = ["trip_id", "fare_amount", "passenger_count"]
    fs, tbl, _ = populated
    ds = dataset(fs, "/d")

    def run(fmt):
        best = None
        for _ in range(3):
            sc = ds.query(format=fmt, num_threads=2).select(cols)
            sc.to_table()
            if best is None or sc.metrics.client_cpu_s < \
                    best.metrics.client_cpu_s:
                best = sc
        return best

    sc_c = run("parquet")
    sc_p = run("pushdown")
    # client path: all CPU on client, none on OSDs
    assert sc_c.metrics.osd_cpu_s == 0
    assert sc_c.metrics.client_cpu_s > 0
    # pushdown: decode CPU on OSDs, client does only IPC materialize
    assert sc_p.metrics.osd_cpu_s > 0
    assert sc_p.metrics.client_cpu_s < sc_c.metrics.client_cpu_s


def test_pushdown_wire_is_larger_at_full_selectivity(populated):
    """Paper Fig. 5, 100% case: Arrow IPC on the wire > compressed ARW1."""
    fs, tbl, layout = populated
    ds = dataset(fs, "/d")
    sc_c = ds.query(format="parquet", num_threads=2)
    sc_c.to_table()
    sc_p = ds.query(format="pushdown", num_threads=2)
    sc_p.to_table()
    assert sc_p.metrics.wire_bytes > sc_c.metrics.wire_bytes


def test_scan_survives_osd_failure(populated):
    fs, tbl, _ = populated
    ds = dataset(fs, "/d")
    fs.store.fail_osd(fs.store.osds[0].osd_id)
    fs.store.fail_osd(fs.store.osds[3].osd_id)
    out = ds.query(format="pushdown", num_threads=4).to_table()
    assert len(out) == len(tbl)               # replicas served everything


def test_empty_result_schema(populated):
    fs, tbl, _ = populated
    ds = dataset(fs, "/d")
    out = ds.query(format="pushdown").filter(
        field("fare_amount") < -5).select("trip_id").to_table()
    assert len(out) == 0
    assert out.schema.names == ["trip_id"]


def test_count_pushdown_matches_scan(populated):
    """COUNT(*) via rowcount_op must equal the materializing count, ship
    only integers, and use metadata-only counts where stats prove ALL."""
    fs, tbl, _ = populated
    ds = dataset(fs, "/d")
    pred = field("fare_amount") > 25.0
    exp = int((tbl.column("fare_amount").values > 25.0).sum())

    q = ds.query(format="pushdown").filter(pred).count()
    got = q.to_scalar()
    assert got == exp
    # only tiny integer payloads crossed the wire
    assert all(t.wire_bytes < 64 for t in q.metrics.tasks)

    # unfiltered count: pure metadata, zero storage calls
    q2 = ds.query(format="pushdown").count()
    assert q2.to_scalar() == len(tbl)
    assert not q2.metrics.tasks

    # range predicate on the monotone column: mix of pruned / ALL / edge
    q3 = ds.query(format="pushdown").filter(field("trip_id") < 3000).count()
    assert q3.to_scalar() == 3000
    assert q3.metrics.fragments_pruned > 0
    # client format falls back to a materializing count
    q4 = ds.query(format="parquet").filter(pred).count()
    assert q4.to_scalar() == exp


def test_projection_only(populated):
    fs, tbl, _ = populated
    ds = dataset(fs, "/d")
    out = ds.query(format="pushdown").select("payment_type").to_table()
    assert out.schema.names == ["payment_type"]
    assert len(out) == len(tbl)
