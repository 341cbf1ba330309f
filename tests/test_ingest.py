"""The ingest plane: deterministic sharding, byte-exact resume through
the checkpoint layer, elastic re-sharding, and QoS coexistence.

The contracts under test are what makes the reader trustworthy as the
training input path: the shard partition is a pure function of the plan
(every fragment exactly once, any dp_size, empty shards legal); a reader
restored from a ReaderState — including one that round-tripped through
CheckpointManager on a snapshot-pinned mutable dataset with a concurrent
append in flight — emits a byte-identical batch stream; a mid-epoch
downsize hands the unconsumed remainder to the survivors exactly once
(orphaned packing buffers adopted, not dropped); and ingest runs as a
bulk tenant that never starves an interactive scanner.
"""

import threading
import types

import numpy as np
import pytest

from repro.aformat.expressions import field
from repro.aformat.table import Table
from repro.core import MutableDataset, dataset, make_cluster
from repro.data import synth_corpus, write_corpus
from repro.dataset.plan import partition_tasks
from repro.dataset.qos import TenantRegistry, ingest_context
from repro.distrib import ANY_SHAPE, CheckpointManager, plan_downsize
from repro.ingest import (ReaderConfig, ReaderState, ShardedReader,
                          epoch_order, reshard_states)

FORMATS = ["parquet", "pushdown", "adaptive"]


@pytest.fixture(scope="module")
def corpus_fs():
    fs = make_cluster(4)
    tbl = synth_corpus(300, mean_doc_len=200, vocab_size=1000, seed=3)
    write_corpus(fs, "/c", tbl, num_shards=4, row_group_rows=4096)
    return fs, tbl


def take(reader, n):
    return [next(reader) for _ in range(n)]


def assert_same_batches(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert np.array_equal(x["tokens"], y["tokens"])
        assert np.array_equal(x["labels"], y["labels"])


# ---------------------------------------------------------------------------
# shard partition properties
# ---------------------------------------------------------------------------


def test_partition_every_task_exactly_once(corpus_fs):
    fs, _ = corpus_fs
    ds = dataset(fs, "/c")
    r = ShardedReader(ds, ReaderConfig(seq_len=32, local_batch=2))
    tasks = r.tasks
    r.close()
    assert len(tasks) > 4
    for dp in (1, 2, 3, 5, 7, 64):
        shards = partition_tasks(tasks, dp)
        assert len(shards) == dp
        flat = [i for s in shards for i in s]
        assert sorted(flat) == list(range(len(tasks)))  # exactly once
        for s in shards:
            assert s == sorted(s)  # plan order within a shard
        # deterministic: same inputs, same partition
        assert partition_tasks(tasks, dp) == shards


def test_partition_row_balanced(corpus_fs):
    fs, _ = corpus_fs
    ds = dataset(fs, "/c")
    r = ShardedReader(ds, ReaderConfig(seq_len=32, local_batch=2))
    tasks = r.tasks
    r.close()
    shards = partition_tasks(tasks, 3)
    loads = [sum(tasks[i].fragment.num_rows for i in s) for s in shards]
    biggest = max(t.fragment.num_rows for t in tasks)
    # greedy LPT: no two shards differ by more than one fragment
    assert max(loads) - min(loads) <= biggest


def test_partition_empty_and_edge_cases():
    assert partition_tasks([], 4) == [[], [], [], []]
    with pytest.raises(ValueError):
        partition_tasks([], 0)


def test_more_ranks_than_fragments_is_legal(corpus_fs):
    """The old TokenPipeline crashed here; empty shards must idle."""
    fs, _ = corpus_fs
    ds = dataset(fs, "/c")
    cfg = ReaderConfig(seq_len=32, local_batch=2)
    probe = ShardedReader(ds, cfg)
    n = len(probe.tasks)
    probe.close()
    dp = n + 5
    covered = []
    empties = 0
    for rank in range(dp):
        rd = ShardedReader(ds, cfg, dp_rank=rank, dp_size=dp)
        covered.extend(rd.shard)
        if not rd.shard:
            empties += 1
            assert list(rd.batches()) == []  # yields nothing, no crash
        rd.close()
    assert empties == 5
    assert sorted(covered) == list(range(n))


# ---------------------------------------------------------------------------
# checkpoint / restore determinism
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fmt", FORMATS)
def test_resume_byte_identical(corpus_fs, fmt):
    """Kill after N batches, restore from the checkpoint state: the
    continuation is byte-identical to the uninterrupted stream — across
    every placement (client, storage, scheduler-placed)."""
    fs, _ = corpus_fs
    ds = dataset(fs, "/c")
    cfg = ReaderConfig(seq_len=48, local_batch=2, format=fmt,
                       predicate=field("quality") > 0.4, seed=9,
                       num_threads=2)
    ref = ShardedReader(ds, cfg)
    full = take(ref, 12)
    ref.close()

    a = ShardedReader(ds, cfg)
    head = take(a, 5)
    st = a.checkpoint()
    a.close()  # the "kill": prefetched-but-undelivered batches are lost

    b = ShardedReader(ds, cfg, state=ReaderState.from_arrays(st.to_arrays()))
    tail = take(b, 7)
    b.close()
    assert_same_batches(head + tail, full)


def test_resume_spans_epoch_boundary(corpus_fs):
    fs, _ = corpus_fs
    ds = dataset(fs, "/c")
    # big batches + a half-size shard: one epoch is only a few batches
    cfg = ReaderConfig(seq_len=512, local_batch=8, seed=1, num_threads=2)
    ref = ShardedReader(ds, cfg, dp_rank=1, dp_size=2)
    full = take(ref, 20)
    assert ref.checkpoint().epoch >= 1  # proved we crossed an epoch
    ref.close()
    a = ShardedReader(ds, cfg, dp_rank=1, dp_size=2)
    head = take(a, 9)
    st = a.checkpoint()
    a.close()
    b = ShardedReader(ds, cfg, state=st)
    tail = take(b, 11)
    b.close()
    assert_same_batches(head + tail, full)


def test_state_arrays_roundtrip():
    for override in (None, np.array([4, 1, 7], np.int64)):
        st = ReaderState(seed=3, dp_rank=1, dp_size=4, epoch=2, cursor=5,
                         snapshot_id=8, n_tasks=40,
                         buffer=np.arange(13, dtype=np.int32),
                         override=override)
        rt = ReaderState.from_arrays(st.to_arrays())
        assert dataclasses_equal(st, rt)


def dataclasses_equal(a: ReaderState, b: ReaderState) -> bool:
    if (a.seed, a.dp_rank, a.dp_size, a.epoch, a.cursor, a.snapshot_id,
            a.n_tasks) != (b.seed, b.dp_rank, b.dp_size, b.epoch,
                           b.cursor, b.snapshot_id, b.n_tasks):
        return False
    if not np.array_equal(a.buffer, b.buffer):
        return False
    if (a.override is None) != (b.override is None):
        return False
    return a.override is None or np.array_equal(a.override, b.override)


def test_state_version_and_plan_guards(corpus_fs):
    arrays = ReaderState(seed=0, dp_rank=0, dp_size=1).to_arrays()
    arrays["meta"] = arrays["meta"].copy()
    arrays["meta"][0] = 99
    with pytest.raises(ValueError, match="version"):
        ReaderState.from_arrays(arrays)
    # a state cut from a different plan shape is refused, not misread
    fs, _ = corpus_fs
    ds = dataset(fs, "/c")
    st = ReaderState(seed=0, dp_rank=0, dp_size=1, n_tasks=3)
    with pytest.raises(ValueError, match="task"):
        ShardedReader(ds, ReaderConfig(seq_len=32, local_batch=2),
                      state=st)


def test_checkpoint_manager_any_shape(corpus_fs):
    """ANY_SHAPE restores a leaf whose shape can't be known up front
    (the variable-length packing buffer); exact structs still enforce."""
    fs, _ = corpus_fs
    cm = CheckpointManager(fs, "/ckpt_any", keep=2)
    cm.save({"buf": np.arange(7, dtype=np.int32)}, 1)
    out = cm.restore({"buf": ANY_SHAPE}, 1)
    assert np.array_equal(out["buf"], np.arange(7, dtype=np.int32))
    with pytest.raises(ValueError, match="expected"):
        cm.restore({"buf": np.zeros(3, np.int32)}, 1)


def test_resume_on_snapshot_pinned_mutable_dataset(corpus_fs):
    """The acceptance criterion: reader state round-trips through
    CheckpointManager alongside a model pytree, on a MutableDataset,
    with a concurrent append landing between checkpoint and restore —
    the restored stream is byte-identical because as_of() pins the
    snapshot the run started from; only a *fresh* reader sees the new
    data."""
    fs, tbl = corpus_fs
    md = MutableDataset.create(fs, "/mut_ingest")
    md.append(tbl, row_group_rows=4096)
    cfg = ReaderConfig(seq_len=48, local_batch=2, seed=4, num_threads=2)

    ref = ShardedReader(md, cfg)
    full = take(ref, 10)
    ref.close()

    a = ShardedReader(md, cfg)
    head = take(a, 4)
    cm = CheckpointManager(fs, "/ckpt_ing", keep=2)
    model = {"w": np.ones((3, 3), np.float32), "step": np.int64(4)}
    cm.save({"model": model, "reader": a.checkpoint().to_arrays()}, 4)
    a.close()

    # a commit lands while the job is down
    extra = synth_corpus(80, mean_doc_len=150, vocab_size=1000, seed=77)
    md.append(extra, row_group_rows=4096)

    restored = cm.restore({"model": {"w": np.zeros((3, 3), np.float32),
                                     "step": np.int64(0)},
                           "reader": ReaderState.restore_structs()}, 4)
    assert np.array_equal(restored["model"]["w"], model["w"])
    rstate = ReaderState.from_arrays(restored["reader"])
    b = ShardedReader(md, cfg, state=rstate)
    assert b.snapshot_id == rstate.snapshot_id  # pinned, not HEAD
    tail = take(b, 6)
    b.close()
    assert_same_batches(head + tail, full)

    # un-pinned readers do see the append
    fresh = ShardedReader(md, cfg)
    assert len(fresh.tasks) > len(b.tasks)
    assert fresh.snapshot_id > rstate.snapshot_id
    fresh.close()


# ---------------------------------------------------------------------------
# elastic re-sharding
# ---------------------------------------------------------------------------


def mesh_stub(data=4, model=1):
    # plan_downsize only reads axis_names and shape — a stub stands in
    # for a real 4-device mesh on this 1-CPU test host
    return types.SimpleNamespace(axis_names=("data", "model"),
                                 shape={"data": data, "model": model})


def test_downsize_covers_remainder_exactly_once(corpus_fs):
    fs, _ = corpus_fs
    ds = dataset(fs, "/c")
    cfg = ReaderConfig(seq_len=32, local_batch=2, seed=6, num_threads=2)
    readers = [ShardedReader(ds, cfg, dp_rank=r, dp_size=4)
               for r in range(4)]
    for r in readers:
        take(r, 2)  # mid-epoch on every rank
    states = [r.checkpoint() for r in readers]
    shards = readers[0].shards
    tasks = readers[0].tasks
    for r in readers:
        r.close()

    plan = plan_downsize(mesh_stub(4, 1), healthy_devices=2)
    new_dp = plan.axis_size("data")
    assert new_dp == 2
    new_states = reshard_states(ds, cfg, states, new_dp)
    assert [s.dp_rank for s in new_states] == [0, 1]

    consumed = []
    for s in states:
        consumed.extend(epoch_order(s, shards)[:s.cursor])
    handed = [int(i) for s in new_states for i in s.override]
    # consumed ∪ handed == the whole epoch, disjointly
    assert sorted(consumed + handed) == sorted(
        i for sh in shards for i in sh)

    # token conservation: pending rows + every rank's packing remainder
    # all land somewhere (dead ranks' buffers adopted, not dropped)
    pending_rows = sum(tasks[i].fragment.num_rows for i in handed)
    assert pending_rows == sum(
        tasks[i].fragment.num_rows
        for s in states for i in epoch_order(s, shards)[s.cursor:])
    assert sum(len(s.buffer) for s in new_states) == \
        sum(len(s.buffer) for s in states)

    # survivors actually stream from the handed-off remainder
    for s in new_states:
        rd = ShardedReader(ds, cfg, state=s)
        batch = next(rd)
        assert batch["tokens"].shape == (2, 32)
        rd.close()


def test_downsize_validation(corpus_fs):
    fs, _ = corpus_fs
    ds = dataset(fs, "/c")
    cfg = ReaderConfig(seq_len=32, local_batch=2)
    states = [ReaderState(seed=0, dp_rank=r, dp_size=4) for r in range(3)]
    with pytest.raises(ValueError, match="all 4 ranks"):
        reshard_states(ds, cfg, states, 2)
    bad = [ReaderState(seed=0, dp_rank=0, dp_size=2),
           ReaderState(seed=1, dp_rank=1, dp_size=2)]
    with pytest.raises(ValueError, match="disagree"):
        reshard_states(ds, cfg, bad, 1)
    with pytest.raises(ValueError, match="at least one"):
        reshard_states(ds, cfg, [], 1)


def test_downsize_to_one_rank_mid_epoch(corpus_fs):
    """Extreme shrink: a single survivor inherits every rank's
    remainder and keeps streaming."""
    fs, _ = corpus_fs
    ds = dataset(fs, "/c")
    cfg = ReaderConfig(seq_len=32, local_batch=2, seed=2, num_threads=2)
    readers = [ShardedReader(ds, cfg, dp_rank=r, dp_size=3)
               for r in range(3)]
    take(readers[0], 3)  # ranks at *different* cut points
    take(readers[1], 1)
    states = [r.checkpoint() for r in readers]
    for r in readers:
        r.close()
    (lone,) = reshard_states(ds, cfg, states, 1)
    assert lone.dp_rank == 0 and lone.dp_size == 1
    rd = ShardedReader(ds, cfg, state=lone)
    out = take(rd, 5)
    rd.close()
    assert all(b["tokens"].dtype == np.int32 for b in out)


# ---------------------------------------------------------------------------
# QoS: ingest as a bulk tenant
# ---------------------------------------------------------------------------


def test_ingest_tenant_does_not_starve_interactive(corpus_fs):
    """A training reader hammering the cluster as the registered bulk
    'ingest' tenant must not starve a deadline-carrying interactive
    tenant: every interactive query completes with a Table, never a
    Shed, while ingest streams concurrently."""
    fs, _ = corpus_fs
    ds = dataset(fs, "/c")
    registry = TenantRegistry(slots_per_osd=2)
    registry.register("dash", weight=4.0, lane="interactive",
                      deadline_s=5.0)
    cfg = ReaderConfig(seq_len=64, local_batch=4, num_threads=4,
                       registry=registry)
    reader = ShardedReader(ds, cfg)
    assert reader.ctx.tenant == "ingest" and reader.ctx.lane == "bulk"

    stop = threading.Event()

    def churn():
        while not stop.is_set():
            next(reader)

    t = threading.Thread(target=churn, daemon=True)
    t.start()
    try:
        for _ in range(5):
            out = ds.query(tenant=registry.context("dash"),
                           num_threads=2).filter(
                field("quality") > 0.5).select("token").to_table()
            assert isinstance(out, Table), f"interactive shed: {out}"
            assert len(out) > 0
    finally:
        stop.set()
        reader.close()
        t.join(timeout=10.0)
    assert not t.is_alive()
    # the registry saw both tenants
    seen = registry.by_tenant()
    assert "dash" in seen and "ingest" in seen


def test_ingest_context_registration():
    registry = TenantRegistry()
    ctx = ingest_context(registry)
    assert ctx.tenant == "ingest" and ctx.lane == "bulk"
    assert ctx.registry is registry
    # idempotent: a second reader reuses the spec
    assert ingest_context(registry).tenant == "ingest"
    assert registry.spec("ingest").lane == "bulk"
    # registry-free fallback still tags the lane
    solo = ingest_context(None)
    assert solo.tenant == "ingest" and solo.registry is None


# ---------------------------------------------------------------------------
# reader surface
# ---------------------------------------------------------------------------


def test_stats_surface(corpus_fs):
    fs, _ = corpus_fs
    ds = dataset(fs, "/c")
    rd = ShardedReader(ds, ReaderConfig(seq_len=32, local_batch=2))
    take(rd, 3)
    st = rd.stats()
    rd.close()
    for key in ("fragments_scanned", "client_cpu_s", "osd_cpu_s",
                "wire_bytes", "rows", "batches", "epochs"):
        assert key in st
    assert st["rows"] > 0 and st["batches"] >= 3


def test_reader_context_manager(corpus_fs):
    fs, _ = corpus_fs
    ds = dataset(fs, "/c")
    with ShardedReader(ds, ReaderConfig(seq_len=32, local_batch=2)) as rd:
        next(rd)
        thread = rd._prefetcher._thread
    assert not thread.is_alive()  # close() joined the prefetch thread


# ---------------------------------------------------------------------------
# one process, four devices: MeshReader reads every rank
# ---------------------------------------------------------------------------

MESH_READER_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
from repro.aformat import parquet
from repro.aformat.decode import NumPyBackend
from repro.aformat.expressions import field
from repro.core import dataset, make_cluster
from repro.data import synth_corpus, write_corpus
from repro.ingest import MeshReader, ReaderConfig, ShardedReader, epoch_order
from repro.launch.mesh import make_local_mesh

fs = make_cluster(4)
write_corpus(fs, "/c", synth_corpus(40, mean_doc_len=150, vocab_size=3000,
                                    seed=5),
             num_shards=4, row_group_rows=512)
ds = dataset(fs, "/c")
pred = field("quality") > 0.3
cfg = ReaderConfig(seq_len=31, local_batch=2, predicate=pred,
                   format="parquet", decode_backend="pallas", num_threads=2)
reader = MeshReader(ds, cfg, make_local_mesh(4, 1))
assert sorted(reader.readers) == [0, 1, 2, 3], sorted(reader.readers)
n_tasks = len(reader.readers[0].tasks)
owned = sorted(i for rd in reader.readers.values() for i in rd.shard)
assert owned == list(range(n_tasks)), owned     # every fragment, one rank


def tokens_of(task):
    # independent of the reader and the executor: the fragment's row group
    # decoded and filtered on the host
    frag = task.fragment
    src = parquet.BytesSource(fs.read_file(frag.path))
    rg = frag.client_meta.row_groups[frag.client_rg_index]
    return NumPyBackend().scan_row_group(
        src, frag.client_meta, rg, ["token"], pred).column("token").values


epoch0 = {}
for r, rd in reader.readers.items():
    order = epoch_order(rd.checkpoint(), rd.shards)
    epoch0[r] = np.concatenate([tokens_of(rd.tasks[i]) for i in order])
ranks = [ShardedReader(ds, cfg, dp_rank=r, dp_size=4) for r in range(4)]
streams = {r: [] for r in range(4)}
for step in range(1000):
    if all(sum(map(len, streams[r])) >= len(epoch0[r]) for r in range(4)):
        break
    got = next(reader)
    host = [next(rd) for rd in ranks]
    for k in ("tokens", "labels"):
        want = np.concatenate([h[k] for h in host])
        assert np.asarray(got[k]).tobytes() == want.tobytes(), (step, k)
    toks, labs = np.asarray(got["tokens"]), np.asarray(got["labels"])
    for r in range(4):
        for row in range(r * 2, r * 2 + 2):     # rank r's rows
            streams[r].append(np.append(toks[row], labs[row][-1]))
for r in range(4):
    stream = np.concatenate(streams[r])
    # each fragment of rank r's shard read once, in its epoch order
    assert np.array_equal(stream[:len(epoch0[r])], epoch0[r]), r
reader.close()
for rd in ranks:
    rd.close()
print("MESH_READER_OK", step, n_tasks)
"""


def test_mesh_reader_reads_every_rank_on_four_devices():
    """One process, four (virtual) devices: the global batch holds all
    four ranks' batches in order, and over one epoch each rank reads
    every fragment of its shard exactly once."""
    from subproc import run_python

    out = run_python(["-c", MESH_READER_CODE], timeout=900)
    assert "MESH_READER_OK" in out.stdout, (out.stdout[-1500:],
                                            out.stderr[-3000:])
