"""Dataset — the Arrow Dataset API analogue (paper §2.2).

Discovery maps a CephFS prefix to a list of self-contained Fragments for
any of the three layouts (flat single-object files, striped, split).
Queries are built lazily through :meth:`Dataset.query` (select / filter /
limit / aggregate / count / join — joins push the build side's keys into
the probe scan as an IN-list or bloom filter), optimized as a logical
plan, and lowered to
per-fragment physical tasks run by the one shared streaming executor
(``repro.dataset.plan``) through whichever FileFormat placement the
caller picked:

* ``format="parquet"``   — client-side decode (the paper's baseline),
* ``format="pushdown"``  — storage-side ``scan_op`` (the paper's RADOS
  Parquet),
* ``format="adaptive"``  — per-fragment placement decided at runtime by
  the :class:`~repro.dataset.scheduler.ScanScheduler` from live OSD load,
  with hedged storage scans and an LRU columnar result cache (this repo's
  extension past the paper's static-placement limitation).
"""

from __future__ import annotations

import struct

from repro.aformat import parquet
from repro.aformat.schema import Schema
from repro.dataset.format import FileFormat
from repro.dataset.fragment import Fragment
from repro.dataset.plan import Query
from repro.storage import layouts
from repro.storage.cephfs import CephFS


# ---------------------------------------------------------------------------
# Discovery
# ---------------------------------------------------------------------------


class Dataset:
    def __init__(self, fs: CephFS, schema: Schema,
                 fragments: list[Fragment], *, layout: str,
                 discovery_bytes: int = 0):
        self.fs = fs
        self.schema = schema
        self._fragments = fragments
        self.layout = layout
        self.discovery_bytes = discovery_bytes

    def fragments(self) -> list[Fragment]:
        return list(self._fragments)

    @property
    def num_rows(self) -> int:
        return sum(f.num_rows for f in self._fragments)

    def query(self, *, format: FileFormat | str = "pushdown",
              num_threads: int = 16, queue_depth: int = 4,
              decode_backend=None, tenant=None) -> Query:
        """Start a lazy query: ``ds.query().select(...).filter(...)
        .limit(n)`` / ``.aggregate(...)`` / ``.count()``, executed via
        ``to_table`` / ``to_batches`` / ``to_scalar`` and inspectable via
        ``explain()``.  ``format`` is a FileFormat instance or one of
        "parquet" (client-side), "pushdown" (storage-side), "adaptive"
        (scheduler-placed; pass an ``AdaptiveFormat`` instance instead to
        keep its result cache warm across queries).  Each run's
        accounting is ``Query.metrics``.  ``decode_backend`` picks the
        client-side decode engine (None/"numpy" for the host path,
        "pallas" for the ``repro.kernels`` accelerator ops) for the
        "parquet" and "adaptive" formats.  ``tenant`` tags the run for
        multi-tenant QoS: a tenant name, a
        :class:`~repro.dataset.qos.TaskContext` (usually
        ``TenantRegistry.context(name)`` — weight, lane, deadline), or
        None for the default tenant."""
        return Query(self, format=format, num_threads=num_threads,
                     queue_depth=queue_depth,
                     decode_backend=decode_backend, tenant=tenant)


def _footer_tail_bytes(fs: CephFS, path: str) -> tuple[parquet.FileMeta, int]:
    """Read just the footer of a flat ARW1 file through CephFS (two range
    reads: length word, then the footer) — returns (meta, bytes_read)."""
    size = fs.file_size(path)
    tail = fs.read_range(path, size - 8, 8)
    (flen,) = struct.unpack("<I", tail[:4])
    raw = fs.read_range(path, size - 8 - flen, flen)
    return parquet.FileMeta.deserialize(raw), flen + 8


def dataset(fs: CephFS, prefix: str, layout: str = "auto") -> Dataset:
    """Discover a dataset under ``prefix``.

    A prefix that carries a snapshot log (``MutableDataset.create`` /
    ``append``) is discovered through its *manifest*, not by re-listing
    the prefix: one HEAD read materializes the current snapshot with
    every footer embedded — exact under concurrent appends, and
    uncommitted or retired data files are invisible.

    Otherwise: auto = split if ``.index`` files exist, else striped if
    the files carry the striped xattr, else flat.
    """
    if layout in ("auto", "mutable"):
        from repro.dataset import snapshot as snapshot_mod

        if snapshot_mod.is_mutable(fs, prefix):
            return snapshot_mod.MutableDataset.open(fs, prefix).as_of()
        if layout == "mutable":
            raise FileNotFoundError(
                f"no mutable dataset (snapshot log) at {prefix!r}")
    paths = fs.listdir(prefix)
    if not paths:
        raise FileNotFoundError(f"no files under {prefix!r}")
    index_paths = [p for p in paths if p.endswith(".index")]
    if layout == "auto":
        if index_paths:
            layout = "split"
        elif any(fs.stat(p).xattrs.get("layout") == "striped"
                 for p in paths):
            layout = "striped"
        else:
            layout = "flat"

    if layout == "split":
        return _discover_split(fs, index_paths)
    if layout == "striped":
        striped = [p for p in paths
                   if fs.stat(p).xattrs.get("layout") == "striped"]
        return _discover_striped(fs, striped)
    flat = [p for p in paths if p.endswith(".arw")
            and fs.stat(p).xattrs.get("layout") not in ("split-part",
                                                        "split-index")]
    return _discover_flat(fs, flat)


def _discover_flat(fs, paths) -> Dataset:
    frags: list[Fragment] = []
    schema = None
    disc = 0
    for path in sorted(paths):
        meta, nbytes = _footer_tail_bytes(fs, path)
        disc += nbytes
        schema = schema or meta.schema
        ino = fs.stat(path)
        for i, rg in enumerate(meta.row_groups):
            obj_idx = rg.offset // ino.stripe_unit
            end_obj = (rg.offset + rg.total_bytes - 1) // ino.stripe_unit
            if obj_idx != end_obj:
                raise ValueError(
                    f"{path}: row group {i} spans objects; write flat "
                    "files with write_flat (single object) or use the "
                    "striped/split layouts")
            frags.append(Fragment(
                path, obj_idx, i, rg.num_rows,
                stats=rg.column_stats(meta.schema),
                footer=None, client_meta=meta, client_rg_index=i))
    return Dataset(fs, schema, frags, layout="flat", discovery_bytes=disc)


def _discover_striped(fs, paths) -> Dataset:
    frags: list[Fragment] = []
    schema = None
    disc = 0
    for path in sorted(paths):
        meta = layouts.read_striped_footer(fs, path)
        ino = fs.stat(path)
        su = ino.stripe_unit
        disc += len(meta.serialize()) + 8
        schema = schema or meta.schema
        for i, rg in enumerate(meta.row_groups):
            obj_idx = rg.offset // su
            # rebase the row group's chunk offsets to the object's origin
            rebased = parquet._shift_group(rg, -obj_idx * su)
            sub = parquet.FileMeta(meta.schema, [rebased], rg.num_rows)
            frags.append(Fragment(
                path, obj_idx, 0, rg.num_rows,
                stats=rg.column_stats(meta.schema),
                footer=sub, client_meta=meta, client_rg_index=i))
    return Dataset(fs, schema, frags, layout="striped",
                   discovery_bytes=disc)


def _discover_split(fs, index_paths) -> Dataset:
    frags: list[Fragment] = []
    schema = None
    disc = 0
    for ipath in sorted(index_paths):
        raw = fs.read_file(ipath)
        disc += len(raw)
        index = layouts.SplitIndex.deserialize(raw)
        schema = schema or index.schema
        for rg in index.row_groups:
            frags.append(Fragment(
                rg["file"], 0, 0, rg["num_rows"], stats=rg["stats"],
                footer=None, client_meta=None, client_rg_index=0))
    return Dataset(fs, schema, frags, layout="split", discovery_bytes=disc)
