"""Adaptive scan scheduler: crossover placement, hedging, result cache,
load accounting, and the serving-side ingest path.

The scheduler's contract extends the paper's: not only does switching
placement never change *what* a scan returns, but the placement itself is
now chosen per fragment from live OSD load — so these tests pin (a)
result equivalence with the static formats, (b) the decision direction
under idle vs saturated storage, (c) hedged re-issue against an injected
straggler, and (d) cache hits that survive only until an object is
overwritten.
"""

import numpy as np
import pytest

from repro.aformat.expressions import field
from repro.aformat.table import Table
from repro.core import (AdaptiveFormat, dataset, make_cluster, write_flat,
                        write_split, write_striped)
from repro.dataset.scheduler import ResultCache, ScanScheduler

WRITERS = {"flat": write_flat, "striped": write_striped,
           "split": write_split}


@pytest.fixture(params=["flat", "striped", "split"])
def populated(request, taxi_table):
    fs = make_cluster(8)
    for i in range(4):
        part = taxi_table.slice(i * 5000, 5000)
        WRITERS[request.param](fs, f"/d/part{i}.arw", part,
                               row_group_rows=1024)
    return fs, taxi_table


@pytest.fixture
def flat_ds(taxi_table):
    fs = make_cluster(8)
    for i in range(4):
        write_flat(fs, f"/d/part{i}.arw", taxi_table.slice(i * 5000, 5000),
                   row_group_rows=1024)
    return fs, dataset(fs, "/d"), taxi_table


# ---------------------------------------------------------------------------
# equivalence: adaptive placement never changes results
# ---------------------------------------------------------------------------


def test_adaptive_matches_static(populated):
    fs, tbl = populated
    ds = dataset(fs, "/d")
    pred = (field("fare_amount") > 25.0) & (field("passenger_count") >= 4)
    mask = ((tbl.column("fare_amount").values > 25.0)
            & (tbl.column("passenger_count").values >= 4))
    out = ds.query(format="adaptive", num_threads=4).filter(pred).select(
        "trip_id", "fare_amount").to_table()
    exp = tbl.filter(mask).select(["trip_id", "fare_amount"])
    o = np.argsort(out.column("trip_id").values)
    e = np.argsort(exp.column("trip_id").values)
    assert np.array_equal(out.column("trip_id").values[o],
                          exp.column("trip_id").values[e])
    assert np.allclose(out.column("fare_amount").values[o],
                       exp.column("fare_amount").values[e])


# ---------------------------------------------------------------------------
# placement crossover
# ---------------------------------------------------------------------------


def test_low_load_prefers_storage(flat_ds):
    """Idle cluster + selective predicate: after the first (exploratory)
    client-side fragment teaches the scheduler the output ratio, the rest
    should be pushed down."""
    fs, ds, _ = flat_ds
    fmt = AdaptiveFormat()
    ds.query(format=fmt, num_threads=4).filter(
        field("fare_amount") > 30.0).select("trip_id").to_table()
    dec = fmt.stats()["decisions"]
    assert dec["osd"] > dec["client"]


def test_saturation_prefers_client(flat_ds):
    """Storage-side queue depth far past thread capacity: the scan must
    run client-side (the paper's crossover, now taken automatically)."""
    fs, ds, tbl = flat_ds
    for osd in fs.store.osds:
        osd.background_load = 32 * osd.threads      # ~32 tenants deep
    fmt = AdaptiveFormat()
    q = ds.query(format=fmt, num_threads=4).filter(
        field("fare_amount") > 30.0).select("trip_id")
    out = q.to_table()
    dec = fmt.stats()["decisions"]
    assert dec["osd"] == 0
    assert dec["client"] == len(q.metrics.tasks)
    assert len(out) == int((tbl.column("fare_amount").values > 30.0).sum())


def test_decisions_follow_pressure_estimate(flat_ds):
    """The estimate itself flips direction with injected pressure."""
    fs, ds, _ = flat_ds
    sched = ScanScheduler(fs)
    frag = ds.fragments()[0]
    # teach the scheduler a selective output ratio so storage looks good
    sched._out_ratio.update(0.05)
    sched._decode_rate_osd.update(150e6)
    sched._decode_rate_client.update(150e6)
    idle = sched.estimate(frag)
    assert idle.where == "osd"
    for osd in fs.store.osds:
        osd.background_load = 64 * osd.threads
    saturated = sched.estimate(frag)
    assert saturated.where == "client"
    assert saturated.pressure > idle.pressure


# ---------------------------------------------------------------------------
# hedging
# ---------------------------------------------------------------------------


def test_hedging_fires_on_straggler(flat_ds):
    fs, ds, tbl = flat_ds
    fmt = AdaptiveFormat()
    # warm the latency history on an idle cluster
    ds.query(format=fmt, num_threads=2).filter(
        field("fare_amount") > 30.0).select("trip_id").to_table()
    # now one node straggles pathologically; min-pressure over replicas
    # keeps the placement storage-side, so hedging must save the tail
    name = fs.object_names("/d/part0.arw")[0]
    straggler = fs.store.primary_of(name)
    straggler.straggle_factor = 1e6
    q = ds.query(format=fmt, num_threads=2).filter(
        field("fare_amount") > 60.0).select("trip_id")
    out = q.to_table()
    assert q.metrics.hedged_tasks > 0
    assert fmt.stats()["hedges"] > 0
    # every hedged task was ultimately served: the result is complete
    assert len(out) == int((tbl.column("fare_amount").values > 60.0).sum())


# ---------------------------------------------------------------------------
# EWMA units: both paths observe (stored fragment bytes -> IPC bytes)
# ---------------------------------------------------------------------------


def test_ewma_units_consistent_across_paths(flat_ds):
    """The storage node runs the same decode code as the client, so the
    shared selectivity estimator must see the *same units* from both
    paths: stored fragment bytes in, Arrow-IPC bytes out.  A client-only
    and a storage-only scheduler therefore learn (near-)identical output
    ratios over the same fragments."""
    fs, ds, _ = flat_ds
    cols = ["trip_id", "fare_amount"]
    pred = field("fare_amount") > 30.0
    frags = ds.fragments()[:6]

    osd_sched = ScanScheduler(fs)
    for f in frags:
        osd_sched._scan_osd(f, cols, pred, osd_sched.estimate(f))
    client_sched = ScanScheduler(fs)
    for f in frags:
        client_sched._scan_client(f, cols, pred)

    r_osd = osd_sched._out_ratio.value(0.0)
    r_client = client_sched._out_ratio.value(0.0)
    assert r_osd > 0 and r_client > 0
    assert r_osd == pytest.approx(r_client, rel=0.05)


def test_ewma_converges_under_mixed_traffic(flat_ds):
    """Alternating storage- and client-routed scans feed one estimator;
    it must converge on the true stored->IPC ratio, not oscillate between
    incompatible unit systems."""
    fs, ds, _ = flat_ds
    cols = ["trip_id", "fare_amount"]
    pred = field("fare_amount") > 30.0
    sched = ScanScheduler(fs)
    for i, f in enumerate(ds.fragments()[:10]):
        if i % 2 == 0:
            sched._scan_osd(f, cols, pred, sched.estimate(f))
        else:
            sched._scan_client(f, cols, pred)
    # ground truth from one fragment: decoded IPC bytes per stored byte
    f0 = ds.fragments()[0]
    tbl, _, ipc = sched._scan_client(f0, cols, pred)
    truth = len(ipc) / sched._frag_bytes(f0)
    assert sched._out_ratio.value(0.0) == pytest.approx(truth, rel=0.35)


# ---------------------------------------------------------------------------
# aggregate pushdown through the scheduler
# ---------------------------------------------------------------------------


def test_adaptive_count_rows_matches_scan(flat_ds):
    fs, ds, tbl = flat_ds
    fmt = AdaptiveFormat()
    pred = field("fare_amount") > 25.0
    exp = int((tbl.column("fare_amount").values > 25.0).sum())
    q = ds.query(format=fmt).filter(pred).count()
    assert q.to_scalar() == exp
    assert q.to_scalar() == len(
        ds.query(format=fmt).filter(pred).to_table())
    # the adaptive count ships integers, not materialized fragments
    assert q.metrics.tasks
    assert all(t.wire_bytes < 64 for t in q.metrics.tasks)


def test_adaptive_count_rows_is_cached(flat_ds):
    fs, ds, tbl = flat_ds
    fmt = AdaptiveFormat()
    pred = field("fare_amount") > 25.0
    first = ds.query(format=fmt).filter(pred).count()
    second = ds.query(format=fmt).filter(pred).count()
    assert first.to_scalar() == second.to_scalar()
    assert sum(1 for t in second.metrics.tasks if t.cached) == \
        len(second.metrics.tasks)
    assert all(t.wire_bytes == 0 for t in second.metrics.tasks)


def test_adaptive_count_rows_metadata_only_without_predicate(flat_ds):
    fs, ds, tbl = flat_ds
    q = ds.query(format=AdaptiveFormat()).count()
    assert q.to_scalar() == len(tbl)
    assert not q.metrics.tasks                  # zero storage calls


# ---------------------------------------------------------------------------
# result cache
# ---------------------------------------------------------------------------


def test_cache_hits_on_repeat_scan(flat_ds):
    fs, ds, _ = flat_ds
    fmt = AdaptiveFormat()
    pred = field("fare_amount") > 30.0
    a = ds.query(format=fmt, num_threads=2).filter(pred).select(
        "trip_id").to_table()
    q = ds.query(format=fmt, num_threads=2).filter(pred).select("trip_id")
    b = q.to_table()
    assert q.metrics.cache_hits == len(q.metrics.tasks)
    assert np.array_equal(np.sort(a.column("trip_id").values),
                          np.sort(b.column("trip_id").values))
    # a different projection/predicate must not hit the same entries
    q2 = ds.query(format=fmt, num_threads=2).filter(pred).select(
        "trip_id", "fare_amount")
    q2.to_table()
    assert q2.metrics.cache_hits == 0


def test_cache_invalidated_by_overwrite(flat_ds):
    fs, ds, _ = flat_ds
    fmt = AdaptiveFormat()
    pred = field("fare_amount") > 30.0
    ds.query(format=fmt, num_threads=2).filter(pred).select(
        "trip_id").to_table()
    # touch one object in place: same bytes, new version
    name = fs.object_names("/d/part0.arw")[0]
    before = fs.store.version_of(name)
    fs.store.put(name, fs.store.get(name))
    assert fs.store.version_of(name) > before
    q = ds.query(format=fmt, num_threads=2).filter(pred).select("trip_id")
    out = q.to_table()
    # fragments of the touched object miss; everything else still hits
    assert 0 < q.metrics.cache_hits < len(q.metrics.tasks)
    assert len(out) == len(ds.query(format="parquet").filter(pred).select(
        "trip_id").to_table())


def test_result_cache_lru_eviction():
    cache = ResultCache(capacity_bytes=100)
    cache.put(("a",), b"x" * 60)
    cache.put(("b",), b"y" * 60)          # evicts a
    assert cache.get(("a",)) is None
    assert cache.get(("b",)) == b"y" * 60
    assert cache.evictions == 1
    cache.put(("huge",), b"z" * 1000)     # larger than capacity: not stored
    assert cache.get(("huge",)) is None
    assert cache.nbytes <= 100


# ---------------------------------------------------------------------------
# load accounting
# ---------------------------------------------------------------------------


def test_load_of_pressure_signals():
    from repro.core import make_cluster
    fs = make_cluster(4)
    store = fs.store
    osd = store.osds[0]
    idle = store.load_of(0)
    assert idle.pressure == 1.0
    osd.background_load = osd.threads            # one pipeline deep
    assert store.load_of(0).pressure == pytest.approx(2.0)
    osd.straggle_factor = 3.0
    assert store.load_of(0).pressure == pytest.approx(6.0)
    osd.down = True
    assert store.load_of(0).pressure == float("inf")


def test_inflight_returns_to_zero(flat_ds):
    fs, ds, _ = flat_ds
    ds.query(format="pushdown", num_threads=4).select("trip_id").to_table()
    assert all(o.inflight == 0 for o in fs.store.osds)


# ---------------------------------------------------------------------------
# serving-side ingest through the scheduler
# ---------------------------------------------------------------------------


def test_ingest_prompts_through_adaptive_scan():
    from repro.serve.engine import ingest_prompts
    fs = make_cluster(4)
    rng = np.random.default_rng(3)
    uids = np.repeat(np.arange(16, dtype=np.int64), 8)
    pos = np.tile(np.arange(8, dtype=np.int32), 16)
    toks = rng.integers(0, 1000, uids.size).astype(np.int32)
    tbl = Table.from_pydict({"uid": uids, "pos": pos, "token": toks})
    write_flat(fs, "/prompts/p0.arw", tbl, row_group_rows=32)
    ds = dataset(fs, "/prompts")
    fmt = AdaptiveFormat()
    reqs, metrics = ingest_prompts(ds, format=fmt)
    assert len(reqs) == 16
    for r in reqs:
        sel = uids == r.uid
        expect = toks[sel][np.argsort(pos[sel], kind="stable")]
        assert np.array_equal(r.prompt, expect)
    # repeat ingest is served from the scheduler's result cache
    reqs2, metrics2 = ingest_prompts(ds, format=fmt)
    assert metrics2.cache_hits == len(metrics2.tasks)
    assert len(reqs2) == 16
