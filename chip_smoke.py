"""Drive the system's main path once on a TPU and check what comes out.

    python chip_smoke.py              # one chip: scan phase + train phase
    python chip_smoke.py --chips 4    # four chips: multi-chip ingest only

Scan phase (one chip).  A NYC-taxi-shaped table (17 columns, about 80 B
a row, generated from ``--seed``) is written in the flat layout over 8
OSDs, in row groups of parquet-mr's default ``parquet.block.size``
(128 MiB, about 1.68M rows).  A filtered projection runs through
``Dataset.query(format="parquet", decode_backend="pallas")`` and once
more through ``format="adaptive"``.  The predicate ANDs comparisons on a
DICT int32 and a DICT float32 column, so dictionary decode, the fused
predicate and the compaction all run on the chip.  The per-column
routing is printed, and the phase fails unless every kernel-eligible
DICT column, the predicate and the compaction took the kernel route,
and unless both results are byte-identical to ``decode_backend="numpy"``.

Train phase (one chip).  ``repro.launch.train`` at gemma3-1b's published
widths, depth cut to 6 layers, reading its corpus through
``ShardedReader(decode_backend="pallas")``; fails on a non-finite loss.

``--chips 4``: one process drives a (data=4, model=1) mesh.  The global
batches of ``MeshReader`` must be byte-identical to the host
concatenation of ``ShardedReader(dp_rank=r, dp_size=4)``, r = 0..3, and
step 1's loss must match the same global batch on one device within
2e-3.

The last line of standard output is one JSON object with the device as
JAX reports it.  With no TPU, or without the rest of the repository
beside it, the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import collections
import json
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.aformat import encodings  # noqa: E402
from repro.aformat.decode import PallasBackend  # noqa: E402
from repro.aformat.expressions import field  # noqa: E402
from repro.aformat.table import Table  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import dataset, make_cluster, write_flat  # noqa: E402
from repro.ingest import MeshReader, ShardedReader  # noqa: E402
from repro.launch import train  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.sharding import default_rules  # noqa: E402

#: parquet-mr's default ``parquet.block.size`` over the table's ~80-byte
#: rows: 1,677,721 rows per row group
ROW_GROUP_ROWS = (128 << 20) // 80
ROW_GROUPS = 8
OSDS = 8
COLUMNS = ["trip_id", "vendor_id", "passenger_count", "trip_distance",
           "pu_location", "tip_amount", "fare_amount", "duration_s"]
#: gemma3-1b at its published widths, cut in depth and sequence only
TRAIN_ARGS = ["--arch", "gemma3-1b", "--mesh", "local", "--layers", "6",
              "--seq", "2048", "--format", "parquet",
              "--decode-backend", "pallas", "--ckpt-every", "1000000"]


def taxi_like_table(n_rows: int, seed: int = 0) -> Table:
    """A NYC-taxi-shaped table of 17 columns drawn from ``seed``."""
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


def build_cluster(num_nodes: int, table: Table, *, rows_per_file: int):
    """Flat layout: one file, one object and one row group per
    ``rows_per_file`` rows, under ``/taxi``."""
    fs = make_cluster(num_nodes)
    n = len(table)
    for i, start in enumerate(range(0, n, rows_per_file)):
        part = table.slice(start, min(rows_per_file, n - start))
        write_flat(fs, f"/taxi/part{i:05d}.arw", part,
                   row_group_rows=rows_per_file)
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


class RoutingRecorder(PallasBackend):
    """The Pallas backend, keeping every row group's routing report."""

    def __init__(self):
        self.reports: list[dict] = []
        self._lock = threading.Lock()

    def scan_row_group(self, src, meta, rg, columns=None, predicate=None,
                       report=None):
        report = {} if report is None else report
        out = super().scan_row_group(src, meta, rg, columns, predicate,
                                     report)
        with self._lock:
            self.reports.append(report)
        return out

    def summary(self) -> dict:
        """Route counts per column, for the predicate and per compacted
        column, over every row group scanned so far."""
        cols: dict = collections.defaultdict(collections.Counter)
        comp: dict = collections.defaultdict(collections.Counter)
        pred: collections.Counter = collections.Counter()
        with self._lock:
            for rep in self.reports:
                for name, route in rep.get("columns", {}).items():
                    cols[name][route] += 1
                if "predicate" in rep:
                    pred[rep["predicate"]] += 1
                for name, route in rep.get("compact", {}).items():
                    comp[name][route] += 1
        return {"columns": {k: dict(v) for k, v in cols.items()},
                "predicate": dict(pred),
                "compact": {k: dict(v) for k, v in comp.items()}}


def sorted_by(tbl, key: str):
    return tbl.take(np.argsort(tbl.column(key).values, kind="stable"))


def kernel_columns(ds, columns) -> list[str]:
    """Columns the kernels must decode: DICT int32/float32 chunks."""
    meta = ds.fragments()[0].client_meta
    out = []
    for name in columns:
        f = meta.schema.field(name)
        enc = meta.row_groups[0].chunks[meta.schema.index(name)].encoding
        if enc == encodings.DICT and f.type in ("int32", "float32"):
            out.append(name)
    return out


def check_routes(summary: dict, must: list[str], label: str):
    """Fail unless the ``must`` columns decoded and compacted on the
    kernels and the predicate was fused, in every row group."""
    bad = [f"{part}:{n}" for part in ("columns", "compact") for n in must
           if set(summary[part].get(n, {"missing": 1})) != {"kernel"}]
    if set(summary["predicate"]) != {"kernel"}:
        bad.append("predicate")
    if bad:
        raise AssertionError(f"{label}: host fallback where the kernels "
                             f"must run: {bad} (routing {summary})")


def scan_phase(*, seed: int, row_groups: int, rows_per_group: int) -> dict:
    t = time.perf_counter()
    table = taxi_like_table(row_groups * rows_per_group, seed)
    fs = build_cluster(OSDS, table, rows_per_file=rows_per_group)
    ds = dataset(fs, "/taxi")
    q90 = float(np.float32(np.quantile(
        table.column("trip_distance").values, 0.9)))
    del table
    print(f"scan: {row_groups} row groups x {rows_per_group} rows "
          f"({row_groups * rows_per_group} rows), flat layout over {OSDS} "
          f"OSDs, built in {time.perf_counter() - t:.3f} s", flush=True)
    pred = (field("passenger_count") >= 2) & (field("trip_distance") > q90)
    must = kernel_columns(ds, COLUMNS)
    print(f"scan: predicate {pred}; kernel-eligible DICT columns {must}")

    def query(fmt, backend):
        return ds.query(format=fmt, decode_backend=backend) \
            .filter(pred).select(*COLUMNS)

    reference = sorted_by(query("parquet", "numpy").to_table(), "trip_id")
    out = {"rows_out": len(reference)}
    for fmt in ("parquet", "adaptive"):
        rec = RoutingRecorder()
        t = time.perf_counter()
        got = query(fmt, rec).to_table()
        cold = time.perf_counter() - t
        summary = rec.summary()
        print(f"scan[{fmt}/pallas]: routing {json.dumps(summary)}")
        # adaptive may place every fragment on the OSDs (host path)
        if fmt == "parquet" or summary["columns"]:
            check_routes(summary, must, fmt)
        same = bytes_identical(sorted_by(got, "trip_id"), reference)
        print(f"scan[{fmt}/pallas]: {len(got)} rows, byte-identical to "
              f"numpy: {same}; first (compiling) query {cold:.3f} s")
        if not same:
            raise AssertionError(f"{fmt}: pallas result differs from numpy")
        out[fmt] = {"identical": same, "routing": summary}
    # warm: every kernel shape is compiled.  The query returns host
    # arrays, copied back from every device result (which blocks until
    # the device is done), so the wall covers the device work.
    q = query("parquet", "pallas")
    t = time.perf_counter()
    got = q.to_table()
    warm = time.perf_counter() - t
    t = time.perf_counter()
    query("parquet", "numpy").to_table()
    host = time.perf_counter() - t
    print(f"scan: warm query wall {warm:.6f} s with pallas, {host:.6f} s "
          f"with numpy ({len(got)} rows out)", flush=True)
    out["warm_s"] = {"pallas": warm, "numpy": host}
    return out


def train_phase(extra_args: list[str]) -> dict:
    args = train.parse_args(TRAIN_ARGS + extra_args)
    res = train.run(args)
    cfg = res["config"]
    full = get_config(args.arch).num_layers
    print(f"train: {cfg.name} d_model={cfg.d_model} heads={cfg.num_heads} "
          f"kv={cfg.num_kv_heads} head_dim={cfg.head_dim} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size}; cuts: layers {full}->{cfg.num_layers} "
          f"(layer 5 is the global one of the 5:1 pattern), "
          f"seq {res['seq']}, global batch {res['batch']}")
    for i, loss in enumerate(res["losses"], 1):
        print(f"train: step {i} loss {loss!r}")
    print(f"train: peak_bytes_in_use {res['peak_bytes_in_use']}")
    if not np.all(np.isfinite(res["losses"])):
        raise AssertionError(f"non-finite loss: {res['losses']}")
    return res


def multichip_phase(extra_args: list[str], *, steps: int = 3) -> dict:
    """MeshReader over every device on the data axis vs the host
    concatenation of one ShardedReader per rank; step-1 loss vs one
    device."""
    args = train.parse_args(TRAIN_ARGS + extra_args)
    cfg, mesh, seq, batch, opt = train.build_model(args)
    _, ds, rcfg = train.build_ingest(args, cfg, seq, batch)
    dp = int(mesh.shape["data"])
    reader = MeshReader(ds, rcfg, mesh)
    ranks = [ShardedReader(ds, rcfg, dp_rank=r, dp_size=dp)
             for r in range(dp)]
    first = None
    try:
        for step in range(steps):
            got = next(reader)
            host = [next(rd) for rd in ranks]
            for k in ("tokens", "labels"):
                want = np.concatenate([h[k] for h in host])
                if np.asarray(got[k]).tobytes() != want.tobytes():
                    raise AssertionError(f"step {step}: global {k} differs "
                                         "from the ranks' concatenation")
            if first is None:
                first = (got, {k: np.concatenate([h[k] for h in host])
                               for k in ("tokens", "labels")})
        print(f"multichip: {steps} global batches {reader.shape} over "
              f"mesh {dict(mesh.shape)} byte-identical to the "
              f"concatenation of {dp} ranks")
    finally:
        reader.close()
        for rd in ranks:
            rd.close()
    rules = default_rules()
    state, _, fn = train.build_training(cfg, mesh, rules, opt)
    loss_mesh = float(fn(state, first[0])[1]["loss"])
    del state
    one = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    state, _, fn = train.build_training(cfg, one, rules, opt)
    loss_one = float(fn(state, first[1])[1]["loss"])
    diff = abs(loss_mesh - loss_one)
    print(f"multichip: step-1 loss {loss_mesh!r} on {dp} devices, "
          f"{loss_one!r} on one; |diff| {diff!r} (limit 2e-3)")
    if not (np.isfinite(loss_mesh) and diff <= 2e-3):
        raise AssertionError("multi-chip step-1 loss does not match")
    return {"loss_mesh": loss_mesh, "loss_one": loss_one}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    print(f"compile cache: {use_compile_cache()}")
    if args.chips == 4:
        multichip_phase(["--batch", "1", "--steps", "1"])
    else:
        scan_phase(seed=args.seed, row_groups=ROW_GROUPS,
                   rows_per_group=ROW_GROUP_ROWS)
        train_phase(["--batch", "2", "--steps", "5"])
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
