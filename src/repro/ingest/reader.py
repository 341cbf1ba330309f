"""Sharded, checkpointable, elastic training readers over the query plan.

``ShardedReader`` replaces the old ``TokenPipeline`` private scan path:
instead of hand-rolled fragment pruning and ``scan_fragment`` calls, one
reader per data-parallel rank

1. pins its source — a ``MutableDataset`` is materialized via
   ``as_of()`` so commits landing mid-run stay invisible and a restore
   re-plans the identical fragment list;
2. lowers ``ds.query().filter(pred).select(column)`` through the full
   optimizer (stats pruning, projection pushdown) to a canonical
   :class:`~repro.dataset.plan.FragmentTask` list;
3. takes its shard of that list via
   :func:`~repro.dataset.plan.partition_tasks` — deterministic,
   row-balanced, empty shards legal — and streams it through the shared
   executor (:func:`~repro.dataset.plan.stream_tasks`) with bounded
   prefetch-ahead, under a registered ``bulk``-lane ingest
   :class:`~repro.dataset.qos.TaskContext` so interactive tenants are
   arbitrated against it by weighted-fair admission;
4. packs the token stream into fixed ``(local_batch, seq_len)``
   batches, tracking a :class:`~repro.ingest.state.ReaderState` that
   makes the whole stream resumable byte-for-byte.

Elasticity: on worker loss, feed every surviving (or checkpointed)
rank's ``ReaderState`` to :func:`reshard_states` with the new dp_size
from ``distrib.elastic.plan_downsize(...).axis_size("data")`` — the
not-yet-consumed remainder of the epoch is re-partitioned across the
survivors, each fragment exactly once, and orphaned packing buffers are
adopted rather than dropped.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
from typing import Any, Iterator, Sequence

import numpy as np

from repro.aformat.expressions import Expr
from repro.dataset.format import FileFormat, resolve_format
from repro.dataset.plan import (PhysicalPlan, ScanMetrics, partition_tasks,
                                stream_tasks)
from repro.dataset.qos import TaskContext, TenantRegistry, ingest_context
from repro.ingest.state import ReaderState


@dataclasses.dataclass
class ReaderConfig:
    """What to read and how to batch it (rank/size travel separately —
    the same config is shared by every rank of a job)."""

    seq_len: int
    local_batch: int
    predicate: Expr | None = None          # e.g. quality > 0.8
    format: FileFormat | str = "pushdown"  # "pushdown"|"parquet"|"adaptive"
    num_threads: int = 4                   # scan prefetch-ahead (in flight)
    queue_depth: int = 4
    seed: int = 0
    prefetch: int = 2                      # batch double-buffer depth
    decode_backend: Any = None             # client decode engine (str name)
    tenant: TaskContext | str | None = None
    registry: TenantRegistry | None = None
    column: str = "token"


def epoch_order(state: ReaderState,
                shards: Sequence[Sequence[int]]) -> list[int]:
    """The exact task order ``state``'s rank walks this epoch: the
    elastic override verbatim if set, else the rank's shard permuted by
    the counter-based RNG ``default_rng((seed, epoch, dp_rank))`` — a
    pure function of the state, so any process reproduces it."""
    if state.override is not None:
        return [int(i) for i in state.override]
    shard = shards[state.dp_rank]
    if not shard:
        return []
    rng = np.random.default_rng((state.seed, state.epoch, state.dp_rank))
    return [shard[int(j)] for j in rng.permutation(len(shard))]


class Prefetcher:
    """Double-buffered background prefetch (compute/IO overlap).

    Unlike its predecessor in ``data/pipeline.py``, an abandoned
    Prefetcher no longer leaks its thread: ``close()`` (also via
    ``with`` or GC) wakes a producer parked on a full queue, joins it,
    and closes the source generator so scan resources unwind."""

    _SENTINEL = object()

    def __init__(self, it: Iterator, depth: int = 2):
        self._q: queue.Queue = queue.Queue(maxsize=max(1, depth))
        self._err: BaseException | None = None
        self._stop = threading.Event()
        self._src = it
        self._thread = threading.Thread(target=self._run, args=(it,),
                                        daemon=True)
        self._thread.start()

    def _put(self, item) -> bool:
        """Bounded put that aborts when the consumer closed us."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _run(self, it):
        try:
            for item in it:
                if not self._put(item):
                    return
        except BaseException as e:  # noqa: BLE001 — re-raised on main thread
            self._err = e
        finally:
            self._put(self._SENTINEL)

    def __iter__(self):
        return self

    def __next__(self):
        if self._stop.is_set():
            raise StopIteration
        item = self._q.get()
        if item is self._SENTINEL:
            if self._err is not None:
                raise self._err
            raise StopIteration
        return item

    def close(self):
        """Unblock and join the producer thread; idempotent."""
        if self._stop.is_set():
            return
        self._stop.set()
        while True:  # drain so a parked producer's next put times out fast
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=10.0)
        closer = getattr(self._src, "close", None)
        if closer is not None:
            try:
                closer()
            except Exception:
                pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def _zero_totals() -> dict:
    return {"fragments_scanned": 0, "client_cpu_s": 0.0, "osd_cpu_s": 0.0,
            "wire_bytes": 0, "rows": 0}


class ShardedReader:
    """One DP rank's checkpointable ingest iterator (see module doc).

    Iterating yields ``{"tokens", "labels"}`` host batches of shape
    ``(local_batch, seq_len)``; ``checkpoint()`` returns the
    :class:`ReaderState` of the last batch *delivered* (not merely
    prefetched), so ``ShardedReader(source, cfg, state=that)`` resumes
    the stream with no gap and no repeat."""

    def __init__(self, source, cfg: ReaderConfig, *, dp_rank: int = 0,
                 dp_size: int = 1, state: ReaderState | None = None):
        if state is not None:
            # the state is authoritative: it pins rank, size and seed to
            # the stream it was cut from
            dp_rank, dp_size = state.dp_rank, state.dp_size
            seed = state.seed
        else:
            seed = cfg.seed
        if not (0 <= dp_rank < dp_size):
            raise ValueError(
                f"bad dp_rank/dp_size: {dp_rank}/{dp_size}")
        self.cfg = cfg
        self.dp_rank, self.dp_size = dp_rank, dp_size
        self.ds = self._pin_snapshot(source, state)
        self.snapshot_id = int(getattr(self.ds, "snapshot_id", -1))
        self.fmt = resolve_format(cfg.format,
                                  decode_backend=cfg.decode_backend)
        self.ctx = self._resolve_ctx(cfg)
        self._plan = self._lower()
        self.tasks = self._plan.tasks
        if state is not None and state.n_tasks >= 0 \
                and state.n_tasks != len(self.tasks):
            raise ValueError(
                f"ReaderState was cut from a {state.n_tasks}-task plan "
                f"but this source lowers to {len(self.tasks)} tasks — "
                "not the same data (snapshot drift or config change)")
        self.shards = partition_tasks(self.tasks, dp_size)
        self.shard = self.shards[dp_rank]
        if state is not None:
            self._state = state.clone()
        else:
            self._state = ReaderState(
                seed=seed, dp_rank=dp_rank, dp_size=dp_size,
                snapshot_id=self.snapshot_id, n_tasks=len(self.tasks))
        self._delivered = self._state.clone()
        self._prefetcher: Prefetcher | None = None
        self._totals = _zero_totals()
        self._live: ScanMetrics | None = None
        self._nbatches = 0

    # -- construction helpers ----------------------------------------------
    @staticmethod
    def _pin_snapshot(source, state: ReaderState | None):
        if not hasattr(source, "as_of"):
            return source  # already an immutable Dataset
        if state is not None and state.snapshot_id >= 0:
            return source.as_of(state.snapshot_id)
        return source.as_of()

    @staticmethod
    def _resolve_ctx(cfg: ReaderConfig) -> TaskContext:
        if isinstance(cfg.tenant, TaskContext):
            return cfg.tenant
        if isinstance(cfg.tenant, str):
            return ingest_context(cfg.registry, tenant=cfg.tenant)
        return ingest_context(cfg.registry)

    def _lower(self) -> PhysicalPlan:
        cfg = self.cfg
        if self.ds.schema is None:  # mutable dataset before first append
            return PhysicalPlan(kind="scan", dataset=self.ds, tasks=[],
                                decisions=[], passes=[])
        q = self.ds.query(format=self.fmt, num_threads=cfg.num_threads,
                          queue_depth=cfg.queue_depth, tenant=self.ctx)
        if cfg.predicate is not None:
            q = q.filter(cfg.predicate)
        return q.select(cfg.column).physical_plan()

    @property
    def shard_tasks(self):
        """This rank's FragmentTasks, plan order."""
        return [self.tasks[i] for i in self.shard]

    # -- the scan plane -----------------------------------------------------
    def _scan(self, order: Sequence[int]) -> Iterator:
        """Stream the tasks named by ``order`` (indices into the
        canonical list) through the shared executor, re-yielded in
        ``order`` — completion order would not be resumable.  A small
        reorder buffer (bounded by ``num_threads``) absorbs the
        difference; the executor still overlaps fragment fetches."""
        if not order:
            return
        tasks = [dataclasses.replace(self.tasks[g], index=i)
                 for i, g in enumerate(order)]
        plan = dataclasses.replace(self._plan, tasks=tasks)
        metrics = ScanMetrics(
            discovery_bytes=self.ds.discovery_bytes,
            fragments_total=len(order),
            tenant=self.ctx.tenant, lane=self.ctx.lane)
        self._live = metrics
        try:
            hold: dict[int, Any] = {}
            nxt = 0
            for task, out in stream_tasks(
                    plan, self.fmt, metrics,
                    max_inflight=self.cfg.num_threads,
                    queue_depth=self.cfg.queue_depth, ctx=self.ctx):
                hold[task.index] = out
                while nxt in hold:
                    yield hold.pop(nxt)
                    nxt += 1
            if metrics.shed is not None:
                raise RuntimeError(f"ingest scan shed: {metrics.shed}")
        finally:
            self._live = None
            self._fold(metrics)

    def _fold(self, metrics: ScanMetrics):
        t = self._totals
        t["fragments_scanned"] += len(metrics.tasks)
        t["client_cpu_s"] += metrics.client_cpu_s
        t["osd_cpu_s"] += metrics.osd_cpu_s
        t["wire_bytes"] += sum(r.wire_bytes for r in metrics.tasks)
        t["rows"] += sum(r.rows_out for r in metrics.tasks)

    # -- the batch plane ----------------------------------------------------
    def _emit(self, st: ReaderState, need: int):
        chunk = st.buffer[:need].reshape(self.cfg.local_batch,
                                         self.cfg.seq_len + 1)
        batch = {"tokens": np.ascontiguousarray(chunk[:, :-1]),
                 "labels": np.ascontiguousarray(chunk[:, 1:])}
        st.buffer = np.array(st.buffer[need:], np.int32, copy=True)
        self._nbatches += 1
        return batch, st.clone()

    def batches(self) -> Iterator[tuple[dict[str, np.ndarray], ReaderState]]:
        """The resumable stream: yields ``(batch, state)`` pairs where
        ``state`` is the exact cut point *after* that batch.  Wrapping
        it in a Prefetcher must not change what ``checkpoint()`` means,
        which is why the state rides alongside each batch instead of
        living on the reader."""
        need = self.cfg.local_batch * (self.cfg.seq_len + 1)
        st = self._state
        # a restored buffer may already hold full batches
        while len(st.buffer) >= need:
            yield self._emit(st, need)
        if not self.shard and st.override is None:
            return  # legal empty shard: rank idles, fleet stays up
        while True:
            order = epoch_order(st, self.shards)
            for tbl in self._scan(order[st.cursor:]):
                toks = np.ascontiguousarray(
                    tbl.column(self.cfg.column).values, np.int32)
                st.cursor += 1
                if len(toks):
                    st.buffer = (np.concatenate([st.buffer, toks])
                                 if len(st.buffer) else toks)
                while len(st.buffer) >= need:
                    yield self._emit(st, need)
            if st.override is not None:
                # elastic remainder drained; fall into normal epochs
                st.override = None
                st.cursor = 0
                if not self.shard:
                    return
            else:
                st.epoch += 1
                st.cursor = 0

    def __iter__(self):
        return self

    def __next__(self) -> dict[str, np.ndarray]:
        if self._prefetcher is None:
            self._prefetcher = Prefetcher(self.batches(),
                                          self.cfg.prefetch)
        batch, st = next(self._prefetcher)
        # the checkpointable cut is the last batch the *consumer* saw,
        # not whatever the background thread ran ahead to
        self._delivered = st
        return batch

    # -- checkpoint / lifecycle --------------------------------------------
    def checkpoint(self) -> ReaderState:
        """State of the last delivered batch — save it (``to_arrays()``)
        with the model; restoring replays the stream from right here."""
        return self._delivered.clone()

    def close(self):
        if self._prefetcher is not None:
            self._prefetcher.close()
            self._prefetcher = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- accounting ---------------------------------------------------------
    def stats(self) -> dict:
        d = dict(self._totals)
        live = self._live
        if live is not None:
            recs = list(live.tasks)
            d["fragments_scanned"] += len(recs)
            d["client_cpu_s"] += sum(r.client_cpu_s for r in recs)
            d["osd_cpu_s"] += sum(r.cpu_s for r in recs
                                  if r.where == "osd")
            d["wire_bytes"] += sum(r.wire_bytes for r in recs)
            d["rows"] += sum(r.rows_out for r in recs)
        d["client_cpu_s"] = round(d["client_cpu_s"], 4)
        d["osd_cpu_s"] = round(d["osd_cpu_s"], 4)
        d["batches"] = self._nbatches
        d["epochs"] = self._state.epoch
        return d


class MeshReader:
    """A mesh's global batches, one :class:`ShardedReader` per position
    of its ``"data"`` axis.

    Position ``r`` of the data axis reads shard ``r`` of ``dp_size``; its
    ``(local_batch, seq_len)`` batch lands on the devices at that
    position (replicated over the other axes), and the global
    ``(dp_size * local_batch, seq_len)`` array is assembled from those
    per-device pieces.  A process reads only the positions its own
    devices hold, so one process driving every chip of a host reads
    every shard, and a multi-host job splits the positions by host.
    ``checkpoint()`` returns one :class:`ReaderState` per position read
    here; pass them back as ``states`` to resume."""

    def __init__(self, source, cfg: ReaderConfig, mesh, *,
                 states: Sequence[ReaderState] | None = None):
        from jax.sharding import NamedSharding, PartitionSpec

        self.cfg = cfg
        self.dp_size = int(mesh.shape["data"])
        self.sharding = NamedSharding(mesh, PartitionSpec("data"))
        self.shape = (self.dp_size * cfg.local_batch, cfg.seq_len)
        ranks = sorted({self._rank(idx) for idx in
                        self.sharding.addressable_devices_indices_map(
                            self.shape).values()})
        by_rank = {s.dp_rank: s for s in states or ()}
        self.readers = {
            r: ShardedReader(source, cfg, dp_rank=r, dp_size=self.dp_size,
                             state=by_rank.get(r))
            for r in ranks}

    def _rank(self, index) -> int:
        """Data-axis position of one device's block of the batch."""
        return (index[0].start or 0) // self.cfg.local_batch

    def __iter__(self):
        return self

    def __next__(self) -> dict:
        import jax

        host = {r: next(rd) for r, rd in self.readers.items()}
        return {k: jax.make_array_from_callback(
                    self.shape, self.sharding,
                    lambda idx, k=k: host[self._rank(idx)][k])
                for k in ("tokens", "labels")}

    def checkpoint(self) -> list[ReaderState]:
        return [rd.checkpoint() for rd in self.readers.values()]

    def stats(self) -> dict:
        """Per-rank ``ShardedReader.stats`` summed over this process's
        positions (epochs: the furthest position)."""
        out: dict = {}
        for rd in self.readers.values():
            for k, v in rd.stats().items():
                out[k] = max(out.get(k, 0), v) if k == "epochs" \
                    else out.get(k, 0) + v
        return out

    def close(self):
        for rd in self.readers.values():
            rd.close()


def reshard_states(source, cfg: ReaderConfig,
                   states: Sequence[ReaderState],
                   new_dp_size: int) -> list[ReaderState]:
    """Elastic re-shard: given *every* rank's checkpointed state (the
    combined checkpoint always holds all of them) and the post-downsize
    dp_size (``DownsizePlan.axis_size("data")``), produce one state per
    surviving rank such that every not-yet-consumed task of the current
    epoch is covered exactly once across the survivors.

    The remainder is collected per rank with :func:`epoch_order` (so a
    rank mid-epoch contributes exactly its unconsumed tail), re-balanced
    with the same :func:`~repro.dataset.plan.partition_tasks` used for
    epoch sharding, and handed out as explicit ``override`` orders.
    Dead ranks' packing-buffer remainders are adopted by
    ``old_rank % new_dp_size`` instead of being dropped.  After the
    overrides drain, every survivor falls into epoch
    ``max(epochs) + 1`` under the normal new-dp_size sharding."""
    if not states:
        raise ValueError("reshard_states needs at least one ReaderState")
    if new_dp_size <= 0:
        raise ValueError(f"new_dp_size must be >= 1, got {new_dp_size}")
    states = sorted(states, key=lambda s: s.dp_rank)
    first = states[0]
    old_dp, seed, snap = first.dp_size, first.seed, first.snapshot_id
    for s in states:
        if (s.dp_size, s.seed, s.snapshot_id) != (old_dp, seed, snap):
            raise ValueError(
                "reshard_states: states disagree on dp_size/seed/"
                "snapshot — not one job's checkpoint")
    if sorted(s.dp_rank for s in states) != list(range(old_dp)):
        raise ValueError(
            f"reshard_states needs all {old_dp} ranks' states, got ranks "
            f"{sorted(s.dp_rank for s in states)}")

    # one probe reader pins the snapshot and lowers the canonical plan
    probe = ShardedReader(source, cfg, state=first)
    try:
        tasks, shards = probe.tasks, probe.shards
        n_tasks = len(tasks)
    finally:
        probe.close()

    pending: list[int] = []
    for s in states:
        pending.extend(epoch_order(s, shards)[s.cursor:])
    assignment = partition_tasks([tasks[i] for i in pending], new_dp_size)
    next_epoch = max(s.epoch for s in states) + 1

    adopted: list[list[np.ndarray]] = [[] for _ in range(new_dp_size)]
    for s in states:
        if len(s.buffer):
            adopted[s.dp_rank % new_dp_size].append(
                np.asarray(s.buffer, np.int32))

    out = []
    for r in range(new_dp_size):
        bufs = adopted[r]
        buffer = (np.concatenate(bufs).astype(np.int32) if bufs
                  else np.empty(0, np.int32))
        override = np.asarray([pending[i] for i in assignment[r]],
                              np.int64)
        out.append(ReaderState(
            seed=seed, dp_rank=r, dp_size=new_dp_size, epoch=next_epoch,
            cursor=0, snapshot_id=snap, n_tasks=n_tasks, buffer=buffer,
            override=override))
    return out
