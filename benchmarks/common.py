"""Shared benchmark scaffolding: the paper's workload + cluster replay.

The workload mirrors §3 of the paper: an NYC-yellow-taxi-shaped table
(17ish columns; we keep the analytically relevant ones), split-style flat
files with one row group per object, scanned at 100% / 10% / 1%
selectivity.  Every scan does the real decode/filter work on this host and
records per-fragment TaskRecords; the ClusterSpec replay (storage.perfmodel)
then maps those measured costs onto the paper's testbed (m510: 8-core
nodes, 10 GbE) to produce Fig. 5/6-comparable numbers.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time

import numpy as np

from repro.aformat.expressions import field
from repro.aformat.table import Table
from repro.core import make_cluster, write_flat

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results",
                           "bench")


def taxi_like_table(n_rows: int, seed: int = 0) -> Table:
    rng = np.random.default_rng(seed)
    return Table.from_pydict({
        "trip_id": np.arange(n_rows, dtype=np.int64),
        "vendor_id": rng.integers(1, 3, n_rows).astype(np.int32),
        "passenger_count": rng.integers(1, 7, n_rows).astype(np.int32),
        "trip_distance": rng.gamma(1.5, 2.0, n_rows).astype(np.float32),
        "rate_code": rng.integers(1, 7, n_rows).astype(np.int32),
        "pu_location": rng.integers(1, 266, n_rows).astype(np.int32),
        "do_location": rng.integers(1, 266, n_rows).astype(np.int32),
        "fare_amount": rng.gamma(2.0, 7.5, n_rows).astype(np.float64),
        "tip_amount": rng.gamma(1.2, 2.5, n_rows).astype(np.float32),
        "tolls_amount": (rng.random(n_rows) < 0.05).astype(np.float32)
        * rng.gamma(2.0, 3.0, n_rows).astype(np.float32),
        "total_amount": rng.gamma(2.2, 8.0, n_rows).astype(np.float64),
        "payment_type": rng.integers(1, 5, n_rows).astype(np.int32),
        "extra": rng.choice([0.0, 0.5, 1.0], n_rows).astype(np.float32),
        "mta_tax": np.full(n_rows, 0.5, np.float32),
        "congestion": (rng.random(n_rows) < 0.3).astype(np.float32) * 2.5,
        "airport_fee": (rng.random(n_rows) < 0.1).astype(np.float32) * 1.75,
        "duration_s": rng.gamma(2.0, 600.0, n_rows).astype(np.float32),
    })


# selectivity -> predicate on the synthetic distribution (gamma quantiles)
def selectivity_predicate(table: Table, frac: float):
    if frac >= 1.0:
        return None
    fares = table.column("fare_amount").values
    thr = float(np.quantile(fares, 1.0 - frac))
    return field("fare_amount") > thr


def build_cluster(num_nodes: int, table: Table, *, rows_per_file: int,
                  row_group_rows: int | None = None):
    """Flat layout, one row group per file per object (paper §3)."""
    fs = make_cluster(num_nodes)
    n = len(table)
    rgr = row_group_rows or rows_per_file
    for i, start in enumerate(range(0, n, rows_per_file)):
        part = table.slice(start, min(rows_per_file, n - start))
        write_flat(fs, f"/taxi/part{i:05d}.arw", part,
                   row_group_rows=rgr)
    return fs


def bytes_identical(a: Table, b: Table) -> bool:
    """Bit-exact table equality (stricter than Table.equals): names,
    dtypes, value bits (strings by value) and validity, column by
    column."""
    if a.schema.names != b.schema.names or len(a) != len(b):
        return False
    for ca, cb in zip(a.columns, b.columns):
        if ca.field.type == "string":
            if list(map(str, ca.values)) != list(map(str, cb.values)):
                return False
        elif (ca.values.dtype != cb.values.dtype
              or ca.values.tobytes() != cb.values.tobytes()):
            return False
        if (ca.validity is None) != (cb.validity is None) or (
                ca.validity is not None
                and not np.array_equal(ca.validity, cb.validity)):
            return False
    return True


def save_result(name: str, payload: dict):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, f"{name}.json"), "w") as f:
        json.dump(payload, f, indent=1)


@dataclasses.dataclass
class Timer:
    t0: float = 0.0

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.elapsed = time.perf_counter() - self.t0
