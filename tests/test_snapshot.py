"""Mutable datasets: snapshot isolation, tombstones, optimistic commits,
and the storage-side compaction engine (``compact_op``).

The invariants under test are the subsystem's contract: every query runs
against exactly one immutable snapshot no matter what commits land under
it, deleted rows never resurface at any placement, concurrent writers
never lose updates (CAS on the manifest head), and compaction rewrites
bytes *inside* the cluster — only footer metadata crosses the client
wire — without perturbing pinned readers or the adaptive scheduler's
version-keyed result cache.
"""

import threading

import numpy as np
import pytest

from repro.aformat.expressions import field
from repro.aformat.table import Table
from repro.core import (
    AdaptiveFormat,
    CommitConflict,
    MutableDataset,
    dataset,
    make_cluster,
)
from repro.dataset.snapshot import Manifest, head_object, is_mutable
from repro.storage.objstore import VersionConflictError


def make_part(lo: int, n: int) -> Table:
    """Deterministic rows: k identifies the row, v = k * 0.5."""
    k = np.arange(lo, lo + n, dtype=np.int64)
    return Table.from_pydict({"k": k, "v": k.astype(np.float64) * 0.5})


def keys_of(table: Table) -> list[int]:
    return sorted(table.column("k").values.tolist())


def check_values(table: Table) -> None:
    k = table.column("k").values.astype(np.float64)
    assert np.array_equal(table.column("v").values, k * 0.5)


@pytest.fixture
def mut():
    fs = make_cluster(8)
    md = MutableDataset.create(fs, "/mut")
    for i in range(8):
        md.append(make_part(i * 100, 100), row_group_rows=100)
    return fs, md


# ---------------------------------------------------------------------------
# append / snapshot basics
# ---------------------------------------------------------------------------


def test_append_and_scan_all_formats(mut):
    _fs, md = mut
    for fmt in ("parquet", "pushdown", "adaptive"):
        out = md.query(format=fmt).to_table()
        assert keys_of(out) == list(range(800))
        check_values(out)


def test_every_query_pins_its_snapshot(mut):
    _fs, md = mut
    q = md.query(format="pushdown")
    md.append(make_part(800, 100))
    # planned and executed after the append, but pinned at build time
    assert len(q.to_table()) == 800
    assert len(md.query(format="pushdown").to_table()) == 900


def test_as_of_time_travel(mut):
    _fs, md = mut
    sid = md.snapshot()
    md.append(make_part(800, 100))
    assert md.as_of(sid).num_rows == 800
    assert md.as_of().num_rows == 900
    with pytest.raises(KeyError):
        md.as_of(10_000)


def test_discovery_reads_manifest_not_listing(mut):
    fs, md = mut
    assert is_mutable(fs, "/mut")
    ds = dataset(fs, "/mut")
    assert ds.layout == "mutable"
    assert ds.snapshot_id == md.snapshot()
    assert keys_of(ds.query(format="pushdown").to_table()) == \
        list(range(800))
    # a stray uncommitted file under the prefix stays invisible
    fs.write_file("/mut/data/orphan.arw", b"junk" * 16)
    assert dataset(fs, "/mut").num_rows == 800


def test_append_validates_schema(mut):
    _fs, md = mut
    bad = Table.from_pydict({"x": np.arange(4, dtype=np.int64)})
    with pytest.raises(ValueError, match="schema mismatch"):
        md.append(bad)
    with pytest.raises(ValueError, match="empty"):
        md.append(make_part(0, 100).slice(0, 0))


def test_empty_dataset_answers_or_refuses_cleanly():
    """A freshly created store (no appends, no schema yet) must answer
    schema-free queries and refuse column-referencing ones loudly."""
    fs = make_cluster(4)
    md = MutableDataset.create(fs, "/fresh")
    assert md.query(format="pushdown").count().to_scalar() == 0
    assert md.query(format="pushdown").to_table().num_rows == 0
    agg = md.query(format="pushdown").aggregate(["count"]).to_table()
    assert int(agg.column("count").values[0]) == 0
    with pytest.raises(ValueError, match="no schema"):
        md.query().select("k")
    with pytest.raises(ValueError, match="no schema"):
        md.query().aggregate([("sum", "k")])
    # serving: sizing an empty prompt store is zero waves, not a crash
    from repro.serve.engine import prompt_lengths

    lens, _ = prompt_lengths(md, format="pushdown")
    assert lens == {}


def test_failed_append_leaks_no_file(mut):
    fs, md = mut
    files_before = set(fs.listdir("/mut/data"))
    bad = Table.from_pydict({"x": np.arange(4, dtype=np.int64)})
    with pytest.raises(ValueError, match="schema mismatch"):
        md.append(bad)
    assert set(fs.listdir("/mut/data")) == files_before


def test_create_twice_fails():
    fs = make_cluster(4)
    MutableDataset.create(fs, "/d")
    with pytest.raises(FileExistsError):
        MutableDataset.create(fs, "/d")
    with pytest.raises(FileNotFoundError):
        MutableDataset.open(fs, "/other")


# ---------------------------------------------------------------------------
# snapshot isolation under concurrent writes
# ---------------------------------------------------------------------------


def test_reader_streams_pinned_snapshot_while_writer_appends(mut):
    """A to_batches() stream started before an append never sees it."""
    _fs, md = mut
    q = md.query(format="pushdown", num_threads=2)
    stream = q.to_batches(max_inflight=1)
    got = [next(stream)]  # stream is live before the writer commits
    md.append(make_part(5000, 64))
    md.delete(field("k") >= 5000)
    got.extend(stream)
    merged = Table.concat(got)
    assert keys_of(merged) == list(range(800))
    check_values(merged)


def test_concurrent_appenders_lose_no_update():
    fs = make_cluster(8)
    MutableDataset.create(fs, "/c")
    writers, per_writer, rows = 4, 6, 50
    errors = []

    def work(w: int) -> None:
        md = MutableDataset.open(fs, "/c")
        try:
            for j in range(per_writer):
                lo = (w * per_writer + j) * rows
                md.append(make_part(lo, rows), row_group_rows=rows)
        except Exception as e:  # pragma: no cover - surfaced below
            errors.append(e)

    threads = [
        threading.Thread(target=work, args=(w,)) for w in range(writers)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    md = MutableDataset.open(fs, "/c")
    assert md.snapshot() == writers * per_writer
    out = md.query(format="pushdown").to_table()
    assert keys_of(out) == list(range(writers * per_writer * rows))
    check_values(out)


def test_optimistic_commit_retries_on_conflict(mut):
    """A commit that loses the HEAD CAS race rebases and retries."""
    _fs, md = mut
    md2 = MutableDataset.open(md.fs, "/mut")
    before = md.snapshot()
    sneaked = {"done": False}

    def mutate(head: Manifest) -> Manifest:
        if not sneaked["done"]:
            sneaked["done"] = True
            md2.append(make_part(9000, 10))  # commits under us
        sid = head.snapshot_id + 1
        return Manifest(
            sid, head.snapshot_id, list(head.files), list(head.tombstones)
        )

    new = md._commit(mutate)
    assert md.commit_conflicts == 1
    assert new.snapshot_id == before + 2  # sneaked commit + ours


def test_put_if_version_is_the_commit_token(mut):
    fs, md = mut
    name = head_object("/mut")
    stale = fs.store.version_of(name)
    md.append(make_part(9000, 10))
    with pytest.raises(VersionConflictError):
        fs.store.put_if_version(name, b"stale manifest", stale)


# ---------------------------------------------------------------------------
# tombstones
# ---------------------------------------------------------------------------


def test_deleted_rows_never_resurface_any_format(mut):
    _fs, md = mut
    pre = md.snapshot()
    md.delete((field("k") >= 150) & (field("k") < 250))
    md.delete(field("k") == 700)
    live = [k for k in range(800) if not (150 <= k < 250) and k != 700]
    for fmt in ("parquet", "pushdown", "adaptive"):
        out = md.query(format=fmt).to_table()
        assert keys_of(out) == live
        check_values(out)
        n = md.query(format=fmt).count().to_scalar()
        assert n == len(live)
    # aggregates see the tombstones too
    agg = md.query(format="pushdown").aggregate([("sum", "k")]).to_table()
    assert int(agg.column("sum_k").values[0]) == sum(live)
    # the pre-delete snapshot still has them
    assert md.as_of(pre).query(format="pushdown").count().to_scalar() \
        == 800


def test_tombstone_applies_only_to_older_files(mut):
    _fs, md = mut
    md.delete(field("k") < 100)  # tombstones file 0 (k 0..99)
    md.append(make_part(0, 50))  # re-inserts k 0..49 *after* the delete
    out = md.query(format="pushdown").to_table()
    assert keys_of(out) == sorted(
        list(range(50)) + list(range(100, 800))
    )


def test_tombstone_pruning_is_exact(mut):
    """Stats-provable tombstones prune whole fragments; untouched
    fragments keep their metadata-only answers."""
    _fs, md = mut
    md.delete(field("k") < 100)  # exactly file 0: stats prove ALL
    q = md.query(format="pushdown").count()
    assert q.to_scalar() == 700
    m = q.metrics
    assert m.fragments_pruned == 1  # the fully-deleted fragment
    # every surviving fragment is metadata-answered (tombstone proven
    # NONE by stats) — zero I/O for the whole count
    assert m.metadata_answers == 7
    assert len(m.tasks) == 0


# ---------------------------------------------------------------------------
# compaction
# ---------------------------------------------------------------------------


def test_compact_exact_and_metadata_only_wire():
    fs = make_cluster(8)
    md = MutableDataset.create(fs, "/big")
    for i in range(8):
        md.append(make_part(i * 2000, 2000), row_group_rows=2000)
    before = md.query(format="pushdown").to_table()
    data_bytes = sum(
        rg.total_bytes
        for f in md._read_head()[0].files
        for rg in f.footer.row_groups
    )
    report = md.compact(target_rows=8000)
    # greedy replica-set binning packs nearly everything; files the
    # cluster topology strands as singletons may legitimately remain
    assert report.files_in >= 6
    assert report.files_out < report.files_in
    assert report.fallbacks == 0 and report.fallback_wire_bytes == 0
    # the offload contract: raw row-group bytes never round-trip to the
    # client — only payload JSON out and footer metadata back
    assert report.wire_bytes < 0.10 * data_bytes
    assert report.rewritten_bytes > 0
    after = md.query(format="pushdown").to_table()
    assert keys_of(after) == keys_of(before) == list(range(16000))
    check_values(after)
    # fewer, right-sized fragments
    assert len(md.as_of().fragments()) < 8


def test_compact_drops_tombstoned_rows_physically(mut):
    _fs, md = mut
    md.delete((field("k") >= 0) & (field("k") < 300))
    report = md.compact(target_rows=400)
    assert report.tombstones_dropped == 1
    head = md._read_head()[0]
    assert head.tombstones == []
    assert sum(f.rows for f in head.files) == 500  # physically gone
    out = md.query(format="pushdown").to_table()
    assert keys_of(out) == list(range(300, 800))


def test_compact_all_rows_deleted_retires_files():
    fs = make_cluster(8)
    md = MutableDataset.create(fs, "/gone")
    for i in range(4):
        md.append(make_part(i * 10, 10))
    md.delete(field("k") >= 0)
    report = md.compact(target_rows=1000)
    assert report.files_in == 4 and report.files_out == 0
    head = md._read_head()[0]
    assert head.files == [] and head.tombstones == []
    assert md.query(format="pushdown").to_table().num_rows == 0


def test_pinned_reader_survives_compaction_and_expire(mut):
    _fs, md = mut
    pre = md.snapshot()
    md.delete(field("k") < 100)
    md.compact(target_rows=400)
    pinned = md.as_of(pre)
    out = pinned.query(format="pushdown").to_table()
    assert keys_of(out) == list(range(800))  # pre-delete, pre-compact
    removed = md.expire()
    assert removed  # the compacted-away small files are gone
    with pytest.raises(KeyError):
        md.as_of(pre)
    # HEAD is untouched by the GC
    assert keys_of(md.query(format="pushdown").to_table()) == \
        list(range(100, 800))


def test_compact_conflicts_with_concurrent_delete(mut):
    """A delete committing mid-compaction must abort the rewrite (its
    keep-predicates are stale), and the orphaned output is cleaned up."""
    _fs, md = mut
    md2 = MutableDataset.open(md.fs, "/mut")
    orig_commit = md._commit

    def racing_commit(mutate, **kw):
        md2.delete(field("k") < 50)
        return orig_commit(mutate, **kw)

    md._commit = racing_commit
    with pytest.raises(CommitConflict):
        md.compact(target_rows=400)
    md._commit = orig_commit
    # nothing committed, nothing leaked: the dataset still answers
    # exactly, and a re-run compacts against the fresh tombstone
    assert keys_of(md.query(format="pushdown").to_table()) == \
        list(range(50, 800))
    report = md.compact(target_rows=400)
    assert report.files_in == 8
    assert keys_of(md.query(format="pushdown").to_table()) == \
        list(range(50, 800))


def test_result_cache_stays_correct_across_compaction(mut):
    """The adaptive scheduler's version-keyed cache: entries for the
    retired objects become unreachable — measured, not assumed."""
    _fs, md = mut
    fmt = AdaptiveFormat()
    warm = md.query(format=fmt).to_table()
    again = md.query(format=fmt).to_table()
    assert keys_of(again) == keys_of(warm)
    sched = fmt.scheduler_for(md.fs)
    hits_before = sched.cache.stats()["hits"]
    assert hits_before > 0  # the repeat scan was served from cache

    head_before = md._read_head()[0]
    md.compact(target_rows=400)
    head_after = md._read_head()[0]
    surviving = {f.path for f in head_before.files} & {
        f.path for f in head_after.files
    }
    expected_hits = sum(
        len(f.footer.row_groups)
        for f in head_after.files
        if f.path in surviving
    )
    q = md.query(format=fmt)
    post = q.to_table()
    assert keys_of(post) == list(range(800))
    check_values(post)
    # only fragments of files the compaction left untouched may hit the
    # cache; every retired object's entry is unreachable (new names, new
    # versions) — measured via the scheduler's own hit counter
    assert q.metrics.cache_hits == expected_hits
    assert sched.cache.stats()["hits"] == hits_before + expected_hits
    # and the new objects' results cache normally afterwards
    q2 = md.query(format=fmt)
    q2.to_table()
    assert q2.metrics.cache_hits == len(q2.metrics.tasks) > 0


# ---------------------------------------------------------------------------
# serving ingest through the transactional path
# ---------------------------------------------------------------------------


def test_append_prompts_and_pinned_ingest():
    from repro.serve.engine import (
        Request,
        append_prompts,
        ingest_prompts,
        prompt_lengths,
    )

    fs = make_cluster(8)
    store = MutableDataset.create(fs, "/prompts")
    rng = np.random.default_rng(7)
    reqs = [
        Request(uid=i, prompt=rng.integers(0, 999, 6 + i).astype(np.int32))
        for i in range(5)
    ]
    sid = append_prompts(store, reqs)
    lens, _ = prompt_lengths(store, format="pushdown")
    assert lens == {i: 6 + i for i in range(5)}
    # second wave commits; the first boundary replays exactly via as_of
    append_prompts(store, [Request(uid=9, prompt=np.arange(3, dtype=np.int32))])
    wave1, _ = ingest_prompts(store.as_of(sid), format="pushdown")
    assert [r.uid for r in wave1] == [0, 1, 2, 3, 4]
    for r, want in zip(wave1, reqs):
        assert np.array_equal(r.prompt, want.prompt)
    wave2, _ = ingest_prompts(store, format="pushdown")
    assert [r.uid for r in wave2] == [0, 1, 2, 3, 4, 9]
