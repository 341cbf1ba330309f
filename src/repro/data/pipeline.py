"""Training ingest (deprecated shim): ``TokenPipeline`` over the new
sharded reader.

The real ingest plane now lives in :mod:`repro.ingest` —
``ShardedReader`` runs every scan through the query plan (stats pruning,
projection pushdown, the shared streaming executor, QoS admission) and
is checkpointable and elastic.  ``TokenPipeline`` remains for one
release as a thin wrapper that preserves the historic constructor and
iterator surface, with one behavior fix: a rank with no fragments is a
legal empty shard (it yields nothing) instead of a crash, so a fleet
with more ranks than fragments stays up.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Iterator

import numpy as np

from repro.aformat.expressions import Expr
from repro.dataset.dataset import Dataset
from repro.dataset.format import (FileFormat, ParquetFormat,
                                  PushdownParquetFormat)
from repro.ingest.reader import Prefetcher, ReaderConfig, ShardedReader


@dataclasses.dataclass
class PipelineConfig:
    seq_len: int
    local_batch: int
    predicate: Expr | None = None          # e.g. quality > 0.8
    format: str = "pushdown"                # "pushdown" | "parquet"
    num_threads: int = 4
    queue_depth: int = 4
    seed: int = 0
    prefetch: int = 2                       # double-buffer depth
    hedge_threshold_s: float | None = None


def _make_format(cfg: PipelineConfig) -> FileFormat:
    if cfg.format == "pushdown":
        return PushdownParquetFormat(hedge_threshold_s=cfg.hedge_threshold_s)
    return ParquetFormat()


class TokenPipeline:
    """Deprecated: use :class:`repro.ingest.ShardedReader`.

    Iterator of {"tokens","labels"} host batches for one DP rank,
    now backed by the sharded reader (same packing, same shapes; shard
    assignment is row-balanced rather than round-robin)."""

    def __init__(self, ds: Dataset, cfg: PipelineConfig, *,
                 dp_rank: int = 0, dp_size: int = 1):
        warnings.warn(
            "TokenPipeline is deprecated; use repro.ingest.ShardedReader "
            "(sharded, checkpointable, elastic, QoS-aware)",
            DeprecationWarning, stacklevel=2)
        if not (0 <= dp_rank < dp_size):
            raise ValueError("bad dp_rank/dp_size")
        self.ds = ds
        self.cfg = cfg
        rcfg = ReaderConfig(
            seq_len=cfg.seq_len, local_batch=cfg.local_batch,
            predicate=cfg.predicate, format=_make_format(cfg),
            num_threads=cfg.num_threads, queue_depth=cfg.queue_depth,
            seed=cfg.seed, prefetch=cfg.prefetch)
        self.reader = ShardedReader(ds, rcfg, dp_rank=dp_rank,
                                    dp_size=dp_size)
        self.fmt = self.reader.fmt
        self.fragments = [t.fragment for t in self.reader.shard_tasks]

    def batches(self) -> Iterator[dict[str, np.ndarray]]:
        """Pack the filtered token stream into (B, S) token/label pairs."""
        for batch, _state in self.reader.batches():
            yield batch

    def __iter__(self):
        return Prefetcher(self.batches(), self.cfg.prefetch)

    def close(self):
        self.reader.close()

    # -- accounting ----------------------------------------------------------------
    def stats(self) -> dict:
        d = self.reader.stats()
        return {k: d[k] for k in ("fragments_scanned", "client_cpu_s",
                                  "osd_cpu_s", "wire_bytes", "rows")}

