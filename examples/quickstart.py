"""Quickstart: the paper's API in ~70 lines.

Build a simulated Ceph cluster, write a columnar dataset in the split
layout, and run the same query three ways — decoding on the client
(ParquetFormat), pushed down into the storage nodes
(PushdownParquetFormat), and with the adaptive scheduler picking the
placement per fragment at runtime (AdaptiveFormat).  Same results; the
CPU moves.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.aformat.expressions import field
from repro.aformat.table import Table
from repro.core import AdaptiveFormat, make_cluster, write_split, dataset


def main():
    # -- a Ceph-like cluster: 8 OSDs, 3-way replication, scan_op loaded ----
    fs = make_cluster(num_osds=8)

    # -- write a table in the split layout (one row group per object) ------
    rng = np.random.default_rng(0)
    n = 100_000
    table = Table.from_pydict({
        "trip_id": np.arange(n, dtype=np.int64),
        "passenger_count": rng.integers(1, 7, n).astype(np.int32),
        "fare_amount": rng.gamma(2.0, 7.5, n).astype(np.float64),
    })
    for i in range(4):
        write_split(fs, f"/taxi/part{i}.arw", table.slice(i * 25_000, 25_000),
                    row_group_rows=4_096)

    # -- discover + query ---------------------------------------------------
    ds = dataset(fs, "/taxi")          # finds the .index files
    print(f"dataset: {ds.num_rows} rows, {len(ds.fragments())} fragments, "
          f"layout={ds.layout}")
    predicate = (field("fare_amount") > 40.0) & \
        (field("passenger_count") >= 5)

    adaptive = AdaptiveFormat()        # keep one instance: its result
                                       # cache persists across scans
    for fmt in ("parquet", "pushdown", adaptive, adaptive):
        q = ds.query(format=fmt).filter(predicate) \
            .select("trip_id", "fare_amount")
        result = q.to_table()
        m = q.metrics
        name = fmt if isinstance(fmt, str) else "adaptive"
        print(f"\n[{name}] rows={len(result)} "
              f"pruned={m.fragments_pruned}/{m.fragments_total} fragments")
        print(f"  client cpu  {m.client_cpu_s * 1e3:8.2f} ms")
        print(f"  storage cpu {m.osd_cpu_s * 1e3:8.2f} ms")
        print(f"  wire        {m.wire_bytes / 1e6:8.2f} MB")
        if m.cache_hits:
            print(f"  result cache hits: {m.cache_hits} "
                  "(repeat scan, no storage I/O)")

    print("\nSwitching the format argument moved decode+filter into the "
          "storage layer — the paper's contribution.  The adaptive "
          "scheduler makes that choice per fragment from live OSD load, "
          "and its second scan was served from the columnar result cache.")

    # -- aggregate pushdown: ship partial states, not columns ---------------
    q = ds.query(format="pushdown").filter(predicate).aggregate(
        ["count", ("sum", "fare_amount"), ("mean", "fare_amount"),
         ("max", "fare_amount")], group_by="passenger_count")
    stats = q.to_table()
    wire = sum(t.wire_bytes for t in q.metrics.tasks)
    print(f"\nGROUP BY passenger_count via agg_op "
          f"({wire / 1e3:.1f} KB on the wire):")
    for i in range(len(stats)):
        print(f"  passengers={stats.column('passenger_count').values[i]} "
              f"count={stats.column('count').values[i]} "
              f"mean_fare={stats.column('mean_fare_amount').values[i]:.2f} "
              f"max_fare={stats.column('max_fare_amount').values[i]:.2f}")
    print("Each OSD folded its fragments into a partial aggregate state; "
          "only those few dozen bytes per fragment crossed the wire.")


if __name__ == "__main__":
    main()
