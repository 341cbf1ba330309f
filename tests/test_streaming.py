"""The concurrent streaming execution engine and per-OSD admission.

``Query.to_batches`` must stream with *bounded in-flight fragments*
driven by consumption (backpressure), ``to_table`` must be a faithful
materialization of the same stream, and the unified admission controller
must gate every placement's per-OSD concurrency — the properties the
millions-of-users ingest path rests on.
"""

import threading
import time

import numpy as np
import pytest

from repro.aformat.expressions import field
from repro.aformat.table import Table
from repro.core import ParquetFormat, dataset, make_cluster, write_flat
from repro.dataset.admission import AdmissionController


@pytest.fixture
def flat_ds(taxi_table):
    fs = make_cluster(8)
    for i in range(4):
        write_flat(fs, f"/d/part{i}.arw", taxi_table.slice(i * 5000, 5000),
                   row_group_rows=1024)
    return fs, dataset(fs, "/d"), taxi_table


class _CountingFormat(ParquetFormat):
    """Client-side format instrumented with concurrent-scan accounting."""

    def __init__(self, delay_s: float = 0.0):
        super().__init__()
        self.delay_s = delay_s
        self.started = 0
        self.inflight = 0
        self.peak = 0
        self._lock = threading.Lock()

    def scan_fragment(self, fs, frag, columns, predicate, ctx=None):
        with self._lock:
            self.started += 1
            self.inflight += 1
            self.peak = max(self.peak, self.inflight)
        try:
            if self.delay_s:
                time.sleep(self.delay_s)
            return super().scan_fragment(fs, frag, columns, predicate, ctx)
        finally:
            with self._lock:
                self.inflight -= 1


# ---------------------------------------------------------------------------
# to_batches: streaming semantics
# ---------------------------------------------------------------------------


def test_to_batches_bounds_inflight(flat_ds):
    fs, ds, _ = flat_ds
    fmt = _CountingFormat(delay_s=0.002)
    q = ds.query(format=fmt, num_threads=16).select("trip_id")
    batches = list(q.to_batches(max_inflight=3))
    assert fmt.peak <= 3
    assert fmt.started == len(ds.fragments())
    assert sum(len(b) for b in batches) == ds.num_rows


def test_to_batches_backpressure(flat_ds):
    """A paused consumer pauses the producer: after pulling one batch, at
    most max_inflight + 1 fragments have ever been issued (the window plus
    the one refill triggered by the consumed batch)."""
    fs, ds, _ = flat_ds
    fmt = _CountingFormat()
    q = ds.query(format=fmt, num_threads=16).select("trip_id")
    it = q.to_batches(max_inflight=2)
    next(it)
    started_after_one = fmt.started
    assert started_after_one <= 3       # 2 in window + 1 refill
    it.close()                          # abandoning the stream is clean
    assert fmt.started <= started_after_one + 2


def test_to_batches_matches_to_table(flat_ds):
    fs, ds, tbl = flat_ds
    pred = field("fare_amount") > 30.0
    q = ds.query(format="pushdown", num_threads=4).filter(pred) \
        .select("trip_id")
    streamed = Table.concat(list(q.to_batches()))
    materialized = q.to_table()
    assert np.array_equal(np.sort(streamed.column("trip_id").values),
                          np.sort(materialized.column("trip_id").values))


def test_to_batches_skips_empty_fragments(flat_ds):
    fs, ds, tbl = flat_ds
    # trip_id < 100 matches only the very first row group
    batches = list(ds.query(format="pushdown", num_threads=4)
                   .filter(field("trip_id") < 100).select("trip_id")
                   .to_batches())
    assert all(len(b) for b in batches)
    assert sum(len(b) for b in batches) == 100


def test_to_table_preserves_plan_order(flat_ds):
    """to_table rides the completion-ordered stream but must reassemble
    fragments in plan order (clients relied on it pre-streaming)."""
    fs, ds, tbl = flat_ds
    out = ds.query(format="parquet", num_threads=8).select(
        "trip_id").to_table()
    vals = out.column("trip_id").values
    assert np.array_equal(vals, np.sort(vals))


# ---------------------------------------------------------------------------
# unified admission control
# ---------------------------------------------------------------------------


def test_admission_controller_bounds_per_osd():
    fs = make_cluster(4)
    ctrl = AdmissionController(fs.store, slots_per_osd=2)
    peak = {"v": 0}
    cur = {"v": 0}
    lock = threading.Lock()

    def worker():
        with ctrl.admit(0):
            with lock:
                cur["v"] += 1
                peak["v"] = max(peak["v"], cur["v"])
            time.sleep(0.005)
            with lock:
                cur["v"] -= 1

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert peak["v"] <= 2
    assert ctrl.admitted == 8
    assert ctrl.waits > 0


@pytest.mark.parametrize("fmt", ["parquet", "pushdown", "adaptive"])
def test_all_formats_honour_admission(flat_ds, fmt):
    """Every placement draws from the same per-OSD slots: with one slot
    per node and a wide thread pool, the scan still completes and the
    controller reports real contention."""
    fs, ds, tbl = flat_ds
    q = ds.query(format=fmt, num_threads=16, queue_depth=1).select(
        "trip_id")
    out = q.to_table()
    assert len(out) == len(tbl)
    assert q.metrics.admission["admitted"] == len(q.metrics.tasks)
    assert q.metrics.admission["slots_per_osd"] == 1


def test_adaptive_cache_hits_skip_admission(flat_ds):
    from repro.core import AdaptiveFormat
    fs, ds, _ = flat_ds
    fmt = AdaptiveFormat()
    ds.query(format=fmt, num_threads=4).select("trip_id").to_table()
    q = ds.query(format=fmt, num_threads=4).select("trip_id")
    q.to_table()
    assert q.metrics.cache_hits == len(q.metrics.tasks)
    assert q.metrics.admission["admitted"] == 0    # never touched a node


# ---------------------------------------------------------------------------
# streaming ingest (serving path)
# ---------------------------------------------------------------------------


def test_ingest_prompts_streams_and_groups():
    from repro.serve.engine import ingest_prompts
    fs = make_cluster(4)
    rng = np.random.default_rng(11)
    uids = np.repeat(np.arange(24, dtype=np.int64), 16)
    pos = np.tile(np.arange(16, dtype=np.int32), 24)
    toks = rng.integers(0, 5000, uids.size).astype(np.int32)
    # shuffle rows so uid groups straddle fragment boundaries
    perm = rng.permutation(uids.size)
    tbl = Table.from_pydict({"uid": uids[perm], "pos": pos[perm],
                             "token": toks[perm]})
    write_flat(fs, "/prompts/p0.arw", tbl, row_group_rows=64)
    ds = dataset(fs, "/prompts")
    reqs, metrics = ingest_prompts(ds, format="pushdown")
    assert len(reqs) == 24
    for r in reqs:
        sel = uids == r.uid
        expect = toks[sel][np.argsort(pos[sel], kind="stable")]
        assert np.array_equal(r.prompt, expect)
