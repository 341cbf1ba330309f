"""Plain reference of a training reader's global batches.

The semantics the reader states, written out in NumPy:

- the tasks are the corpus's row groups in file order, then row-group
  order; one with no row that passes the predicate is dropped;
- the tasks are split over the data-axis positions largest first, each
  onto the position with the fewest rows so far (ties: lowest position,
  then lowest task), and each position keeps its tasks in task order;
- in epoch ``e`` position ``r`` reads its tasks in the order
  ``default_rng((seed, e, r)).permutation``, keeps the tokens of the
  rows that pass, and appends them to one stream that runs on across
  epochs;
- batch ``k`` of a position is window ``k`` of ``local_batch * (seq_len
  + 1)`` tokens of its stream, as ``local_batch`` rows; ``tokens`` are a
  row without its last token and ``labels`` without its first.

The control reads the same stream with token ids and the predicate's
float32 column rounded to bfloat16, the next precision below the one the
configuration states.
"""

from __future__ import annotations

import heapq

import ml_dtypes
import numpy as np

from perfbench.references import predicate


def tasks(shards: list[dict[str, np.ndarray]], row_group_rows: int,
          terms, column: str = "token", low_precision: bool = False
          ) -> list[tuple[int, np.ndarray]]:
    """(rows, kept tokens) of every task, in plan order."""
    out = []
    for cols in shards:
        n = len(cols[column])
        for lo in range(0, n, row_group_rows):
            part = {k: v[lo:lo + row_group_rows] for k, v in cols.items()}
            if low_precision:
                part = {k: _bf16(v) for k, v in part.items()}
            keep = predicate.mask(terms, part)
            if keep.any():
                out.append((len(keep), part[column][keep]))
    return out


def _bf16(v: np.ndarray) -> np.ndarray:
    if v.dtype in (np.float32, np.int32):
        return v.astype(ml_dtypes.bfloat16).astype(v.dtype)
    return v


def split(rows: list[int], parts: int) -> list[list[int]]:
    """Largest first onto the lightest part; ties on part, then task."""
    out: list[list[int]] = [[] for _ in range(parts)]
    heap = [(0, p) for p in range(parts)]
    for i in sorted(range(len(rows)), key=lambda i: (-rows[i], i)):
        load, p = heapq.heappop(heap)
        out[p].append(i)
        heapq.heappush(heap, (load + rows[i], p))
    return [sorted(p) for p in out]


class Stream:
    """The batches of one data-axis position."""

    def __init__(self, kept: list[np.ndarray], shard: list[int], *,
                 seed: int, rank: int, local_batch: int, seq_len: int):
        self.kept, self.shard = kept, shard
        self.seed, self.rank = seed, rank
        self.shape = (local_batch, seq_len + 1)
        self.need = local_batch * (seq_len + 1)
        self.tokens = np.zeros(0, np.int32)
        self.epoch = 0

    def batch(self, k: int) -> dict[str, np.ndarray]:
        while len(self.tokens) < (k + 1) * self.need:
            if not self.shard:
                raise ValueError(f"position {self.rank} reads no task")
            rng = np.random.default_rng((self.seed, self.epoch, self.rank))
            order = [self.shard[j] for j in rng.permutation(len(self.shard))]
            self.tokens = np.concatenate(
                [self.tokens] + [self.kept[i] for i in order]).astype(
                    np.int32)
            self.epoch += 1
        rows = self.tokens[k * self.need:(k + 1) * self.need].reshape(
            self.shape)
        return {"tokens": rows[:, :-1], "labels": rows[:, 1:]}


def streams(shards, *, row_group_rows: int, terms, positions: int,
            seed: int, local_batch: int, seq_len: int,
            low_precision: bool = False) -> list[Stream]:
    found = tasks(shards, row_group_rows, terms,
                  low_precision=low_precision)
    parts = split([r for r, _ in found], positions)
    kept = [t for _, t in found]
    return [Stream(kept, parts[r], seed=seed, rank=r,
                   local_batch=local_batch, seq_len=seq_len)
            for r in range(positions)]
