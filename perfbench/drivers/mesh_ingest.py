"""A training host's reader feeding its chips: global batches from
``MeshReader``, pulled as fast as they come.

The configuration gives the corpus (shards, row groups, documents, the
vocabulary), the cluster, and the batch shape per data-axis position;
the traffic mix gives the reader's placement (``format``,
``decode_backend``), its predicate and the mesh.  One ``ShardedReader``
per data-axis position reads its shard of the row groups through the
executor; the window pulls global batches and waits until each is on
its devices.

End-to-end metric: ``tokens_per_s``, the tokens of every global batch
resident on the devices by the end of the window, over the window.
"""

from __future__ import annotations

import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from perfbench import datagen, harness, store
from perfbench.drivers import predicate_expr
from perfbench.recorder import RecordingBackend
from perfbench.references import mesh_ingest as reference
from perfbench.references import predicate as predicate_ref


class Driver:
    def __init__(self, cell: harness.Cell, seed: int):
        self.config, self.traffic, self.seed = cell.config, cell.traffic, seed
        self.backend = None
        if self.traffic.get("decode_backend") == "pallas":
            self.backend = RecordingBackend()
        self.batches: list[tuple[float, float, dict]] = []
        self.cpu_s = 0.0

    # -- set-up -------------------------------------------------------------
    def setup(self):
        from repro.aformat.table import Table
        from repro.core import dataset
        from repro.data import CORPUS_SCHEMA
        from repro.dataset.qos import TenantRegistry, ingest_context
        from repro.ingest import MeshReader, ReaderConfig
        from repro.launch.mesh import make_mesh

        cfg, tr = self.config, self.traffic
        shards = [None] * cfg["shards"]
        t0 = time.perf_counter()

        def make(i):
            def table():
                shards[i] = datagen.corpus_shard(cfg, self.seed, i)
                return Table.from_pydict(shards[i], CORPUS_SCHEMA)
            return table

        self.fs = store.build(
            cfg, [(f"/{cfg['name']}/shard{i:05d}.arw", make(i))
                  for i in range(cfg["shards"])],
            row_group_rows=cfg["row_group_rows"])
        self.terms = predicate_ref.resolve(
            tr["predicate"], lambda c: np.concatenate([s[c] for s in shards]))
        keep = {"token"} | {c for c, _, _ in self.terms}
        self.data = [{k: s[k] for k in keep} for s in shards]
        del shards
        registry = TenantRegistry()
        self.rcfg = ReaderConfig(
            seq_len=cfg["seq_len"], local_batch=cfg["local_batch"],
            predicate=predicate_expr(self.terms), format=tr["format"],
            num_threads=cfg["reader_threads"],
            prefetch=cfg["reader_prefetch"], seed=self.seed,
            decode_backend=self.backend or tr.get("decode_backend"),
            tenant=ingest_context(registry), registry=registry)
        shape = (tr["mesh"]["data"], tr["mesh"]["model"])
        self.mesh = make_mesh(shape, ("data", "model"),
                              devices=jax.devices()[:shape[0] * shape[1]])
        self.reader = MeshReader(dataset(self.fs, f"/{cfg['name']}"),
                                 self.rcfg, self.mesh)
        self.batch_tokens = int(np.prod(self.reader.shape))
        t1 = time.perf_counter()
        # one whole epoch: every row group's shapes, on every position
        self.consumed = 0
        while any(rd.stats()["fragments_scanned"] < len(rd.shard)
                  for rd in self.reader.readers.values()):
            jax.block_until_ready(next(self.reader))
            self.consumed += 1
        self.notes = {"store_s": t1 - t0, "warm_s": time.perf_counter() - t1,
                      "warm_batches": self.consumed}

    # -- the window ------------------------------------------------------------
    def recording(self, on: bool):
        if self.backend is not None:
            self.backend.recording = on

    def window(self, seconds: float) -> dict:
        cpu0 = self.reader.stats()["client_cpu_s"]
        start = time.perf_counter()
        deadline = start + seconds
        while (t0 := time.perf_counter()) < deadline:
            with TraceAnnotation("bench.reader_next"):
                batch = next(self.reader)
            with TraceAnnotation("bench.place"):
                jax.block_until_ready(batch)
            self.batches.append((t0, time.perf_counter(), batch))
        self.cpu_s = self.reader.stats()["client_cpu_s"] - cpu0
        done = sum(1 for _, t1, _ in self.batches if t1 <= deadline)
        self.notes.update(batches=len(self.batches), done_in_window=done)
        return {"tokens_per_s": harness.rate(done * self.batch_tokens,
                                             seconds)}

    @property
    def attempted(self) -> int:
        return len(self.batches)

    # -- per-layer counters ----------------------------------------------------
    def counters(self) -> dict:
        b = self.backend
        return {"tokens": self.batch_tokens * len(self.batches),
                "reader_client_cpu_s": self.cpu_s,
                "reports": list(b.reports) if b else [],
                "decode_calls": list(b.decode_calls) if b else [],
                "pack_calls": list(b.pack_calls) if b else []}

    # -- correctness -------------------------------------------------------------
    def release(self):
        """Stop the readers and free the store; the batches stay."""
        self.reader.close()
        self.reader = self.fs = None

    def check(self, control: bool = False) -> list[harness.Check]:
        """Every global batch of the window, shard by shard, against the
        reference's batch of the position that shard stands for, and on
        that position's device; with ``control``, against the control's."""
        cfg, dp = self.config, self.traffic["mesh"]["data"]
        want = reference.streams(
            self.data, row_group_rows=cfg["row_group_rows"],
            terms=self.terms, positions=dp, seed=self.seed,
            local_batch=cfg["local_batch"], seq_len=cfg["seq_len"],
            low_precision=control)
        devices = self.mesh.devices
        differing = 0
        for i, (_, _, batch) in enumerate(self.batches):
            k = self.consumed + i
            bad = False
            for key, arr in batch.items():
                for sh in arr.addressable_shards:
                    r = (sh.index[0].start or 0) // cfg["local_batch"]
                    bad |= sh.device not in devices[r].tolist()
                    got, exp = np.asarray(sh.data), want[r].batch(k)[key]
                    bad |= not (got.dtype == exp.dtype
                                and np.array_equal(got, exp))
            differing += bad
        self.failed = differing
        return [harness.Check("batches_differing", differing, 0)]
