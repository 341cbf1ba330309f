"""Decode engine: pluggable backends behind every client-side scan.

One row group is the unit of work: decompressed column-chunk buffers plus
their encodings (and an optional predicate) go in, a filtered ``Table``
comes out.  Two backends implement that contract:

``NumPyBackend``
    The host path — ``encodings.decode`` per column, ``Expr.evaluate``
    for the mask, ``Table.filter`` for the selection.  This is the code
    that used to live inline in ``parquet.scan_row_group``; storage-side
    ``scan_op`` still runs it (OSDs have no accelerator).

``PallasBackend``
    The accelerator path (``repro.kernels``): DICT (and, after a host
    width-bit unpack of the index buffer, DICTP) columns batch through
    the ``decode_dictionary`` gather kernel, supported predicates lower
    via ``build_program``/``fused_predicate`` so mask evaluation fuses
    across columns in one pass, and selections compact through
    ``pack_tokens``.  Everything the kernels cannot express — RLE/DELTA
    byte streams, strings, float64, integers outside the f32-exact
    domain, IsIn/Bloom/mixed-logic expression nodes — falls back
    per-column / per-predicate to the host path.  The kernels compile
    for the TPU; on the CPU they run in Pallas's interpreter, which
    exists for tests and rehearsals and is no measure of speed.  Any
    other platform raises (``repro.kernels.platform``).  Every route
    is bit-exact, so the two backends are byte-identical;
    ``tests/test_decode.py`` pins that equivalence across the encoding
    x dtype x validity x predicate grid.

Where a predicate is given, a projected column that the predicate does
not read may be decoded after the selection (late materialization): its
codes are compacted with the other projected columns and only the kept
codes are decoded.  The backend decides per chunk (``defers``): the
Pallas backend defers exactly the chunks its gather kernel decodes, the
host path defers none.

A row group's column chunks are read on the task's thread, in column
order (``read_chunks``).  Where the source is client-side
(``FileSource.client_side``), every buffer of at least
``POOL_MIN_BYTES`` is handed to one process-wide pool of threads as soon
as it is read, and a column is decoded as soon as its own buffers are
inflated, while the later columns' buffers still inflate on the pool.
An OSD's object-class call inflates every buffer on its own thread: that
thread is the OSD's CPU budget, and its wall time is the OSD's CPU.

Each stage is a host span (``repro.trace``): ``repro.decode.decompress``
per buffer, on the thread that inflates it (a pool thread or the task's),
``repro.decode.wait`` per pooled buffer where the task's thread blocks on
its inflate, ``repro.decode.host`` per host route (a column's decode, a
predicate, a host ``take``), ``repro.kernel.dict_decode`` /
``repro.kernel.predicate`` / ``repro.kernel.pack`` per kernel call, from
the host casts to the NumPy result, and nested in those
``repro.kernel.fetch``, the blocking read of the result from the device.
A client task's wall time thus holds the decompression that the pool did
not hide (``repro.decode.wait``), not the inflate of every buffer.

The scheduler prices the two regimes separately: each backend carries a
``decode_rate_prior`` (stored bytes per second of decode+filter) that
seeds the client-side EWMA in ``repro.dataset.scheduler``.
"""

from __future__ import annotations

import dataclasses
import os
import threading
from concurrent.futures import Future, ThreadPoolExecutor, wait
from typing import Iterator, Sequence

import numpy as np

from repro.aformat import compression, encodings
from repro.aformat.expressions import And, Cmp, Expr, Not, Or
from repro.aformat.schema import Field, Schema, to_physical
from repro.aformat.table import Column, Table
from repro.trace import span

#: |integers| below this round-trip float32 exactly — the kernels compute
#: in f32, so columns/constants outside the domain stay on the host path.
F32_EXACT = 2 ** 24

#: Expression ops -> kernel Term ops (repro.kernels.predicate_fused).
_KERNEL_OPS = {"<": "lt", "<=": "le", ">": "gt", ">=": "ge",
               "==": "eq", "!=": "ne"}

#: Numeric types the kernels can represent exactly (f32 compute): bool
#: and f32 always, 32/64-bit ints only inside the f32-exact domain —
#: checked against the live values.  float64 would truncate, so: host.
_KERNEL_TYPES = ("int32", "int64", "float32", "bool")


def _kernel_type(field: Field) -> bool:
    """Whether a column's stored type can take a kernel route: the one
    gate of every route, on the physical type, so a date32 or decimal64
    column routes as the int32 or int64 it is stored as."""
    return field.physical in _KERNEL_TYPES


#: Compressed size from which a buffer of a client-side read is inflated on
#: the pool rather than on the task's thread.  A hand-off to an idle pool
#: and back costs about 56 us, and one core inflates about 65 MB of ZLIB
#: input a second (both measured on an 8-core Xeon host): at 64 KiB the
#: hand-off is about 5% of the inflate it moves, and the smaller buffers
#: (dictionaries, validity bitmaps) stay on the task's thread.
POOL_MIN_BYTES = 64 << 10


def n_data_buffers(field_type: str, encoding: str) -> int:
    """How many of a chunk's buffers hold data (the rest is validity)."""
    if encoding == encodings.PLAIN:
        return 2 if field_type == "string" else 1
    if encoding in (encodings.DICT, encodings.DICTP):
        return 3 if field_type == "string" else 2
    if encoding in (encodings.DELTA, encodings.RLE):
        return 2
    # bitpack: bool is a single bit buffer; integers carry a
    # <base, width> header buffer plus the packed bits
    return 1 if field_type == "bool" else 2


@dataclasses.dataclass
class ChunkData:
    """One column chunk of one row group: decompressed, not yet decoded."""

    field: Field
    encoding: str
    bufs: list[bytes]           # data buffers, then optional validity
    num_rows: int

    @property
    def data_bufs(self) -> list[bytes]:
        return self.bufs[:n_data_buffers(self.field.type, self.encoding)]

    def validity(self) -> np.ndarray | None:
        nd = n_data_buffers(self.field.type, self.encoding)
        if len(self.bufs) <= nd:
            return None
        return np.unpackbits(np.frombuffer(self.bufs[nd], np.uint8)
                             )[:self.num_rows].astype("?")


def _inflate(codec: str, raw: bytes) -> bytes:
    with span("repro.decode.decompress"):
        return compression.decompress(codec, raw)


_pool: ThreadPoolExecutor | None = None
_pool_lock = threading.Lock()


def decompress_pool() -> ThreadPoolExecutor:
    """The process-wide pool that inflates client-side buffers, one thread
    per core this process may run on; made on first use."""
    global _pool
    with _pool_lock:
        if _pool is None:
            _pool = ThreadPoolExecutor(len(os.sched_getaffinity(0)),
                                       thread_name_prefix="repro-inflate")
        return _pool


def _forget_pool():
    # a forked child has none of its parent's pool threads
    global _pool, _pool_lock
    _pool, _pool_lock = None, threading.Lock()


os.register_at_fork(after_in_child=_forget_pool)


def _pooled(chunk, ln: int) -> bool:
    return chunk.codec != compression.NONE and ln >= POOL_MIN_BYTES


def read_chunks(src, meta, rg, names: Sequence[str],
                report: dict | None = None) -> Iterator[ChunkData]:
    """Read + decompress the column chunks ``names`` of one row group,
    yielding each in that order as soon as its buffers are inflated
    (``meta``/``rg`` are the ``parquet.FileMeta``/``RowGroupMeta`` footer
    objects, duck-typed so this module never imports the file format).

    Every byte range is read on this thread first.  Where ``src`` is
    client-side and at least two buffers reach ``POOL_MIN_BYTES``, those
    are inflated on ``decompress_pool()``, each submitted as it is read;
    every other buffer is inflated here when its column is yielded.
    ``report``, when given, gets ``"decompress"``: the compressed bytes
    inflated on the pool and here.  An inflate's error is raised here;
    the row group's other buffers are then cancelled or waited for, so
    none is still inflating when the error (or an early ``close()``)
    leaves this generator."""
    chunks = [rg.chunks[meta.schema.index(n)] for n in names]
    pool = None
    if getattr(src, "client_side", False) and sum(
            _pooled(c, ln) for c in chunks for ln in c.buffer_lengths) > 1:
        pool = decompress_pool()
    sizes = {"pool_bytes": 0, "inline_bytes": 0}
    pending: list[list] = []        # per chunk, per buffer: bytes or Future
    try:
        for c in chunks:
            bufs = []
            off = c.offset
            for ln in c.buffer_lengths:
                raw = src.read(off, ln)
                off += ln
                if pool is not None and _pooled(c, ln):
                    bufs.append(pool.submit(_inflate, c.codec, raw))
                    sizes["pool_bytes"] += ln
                else:
                    bufs.append(raw)
                    sizes["inline_bytes"] += ln
            pending.append(bufs)
        if report is not None:
            report["decompress"] = sizes
        for name, c, bufs in zip(names, chunks, pending):
            out = []
            for b in bufs:
                if isinstance(b, Future):
                    with span("repro.decode.wait"):
                        out.append(b.result())
                else:
                    out.append(_inflate(c.codec, b))
            yield ChunkData(meta.schema.field(name), c.encoding, out,
                            rg.num_rows)
    finally:
        futures = [b for bufs in pending for b in bufs
                   if isinstance(b, Future)]
        for f in futures:
            f.cancel()
        wait(futures)


def _host_decode(chunk: ChunkData) -> np.ndarray:
    """A column chunk's values decoded on the host."""
    with span("repro.decode.host"):
        return encodings.decode(chunk.field.physical, chunk.encoding,
                                chunk.data_bufs, chunk.num_rows,
                                chunk.field.numpy_dtype)


def _dict_codes(chunk: ChunkData) -> np.ndarray:
    """A DICT or DICTP chunk's codes as int32 (DICTP's width-bit unpack
    is a byte-stream transform, on the host)."""
    buf = chunk.data_bufs[0]
    if chunk.encoding == encodings.DICT:
        return np.frombuffer(buf, np.int32)[:chunk.num_rows]
    return encodings.unpack_width(buf[1:], chunk.num_rows,
                                  buf[0]).astype(np.int32)


def _bucket(n: int) -> int:
    """``n`` rounded up to a power of two (0 stays 0): the kernels are
    jitted per shape, so a selection's shapes are bucketed to keep the
    trace cache hot, and a slice restores the exact length."""
    return 1 << (n - 1).bit_length() if n else 0


def _decode_kept(chunk: ChunkData, codes: Column, decode) -> Column:
    """``chunk``'s values at the kept rows, whose compacted codes
    ``codes`` holds, decoded by ``decode`` through the chunk's dictionary.
    The codes are padded with code 0 to the compaction's bucket, a shape
    the warm-up compiled, and the values cut back to the kept rows."""
    k = len(codes)
    padded = np.zeros(_bucket(k), np.int32)
    padded[:k] = codes.values
    col = decode(ChunkData(chunk.field, encodings.DICT,
                           [padded.tobytes(), *chunk.data_bufs[1:]],
                           len(padded)))
    return Column(chunk.field, col.values[:k], codes.validity)


# ---------------------------------------------------------------------------
# Backend interface
# ---------------------------------------------------------------------------


class DecodeBackend:
    """Decode + filter + select one row group.  Subclasses override the
    three hooks (column decode, mask evaluation, selection compaction);
    the row-group template is shared so the backends can never disagree
    about column ordering, validity handling, or projection."""

    name = "abstract"
    #: stored-bytes/s prior seeding the scheduler's client-side EWMA
    decode_rate_prior = 150e6

    def decode_column(self, chunk: ChunkData) -> Column:
        raise NotImplementedError

    def evaluate_predicate(self, tbl: Table, predicate: Expr,
                           report: dict | None = None) -> np.ndarray:
        raise NotImplementedError

    def compact(self, tbl: Table, mask: np.ndarray,
                report: dict | None = None) -> Table:
        raise NotImplementedError

    def defers(self, chunk: ChunkData) -> bool:
        """Whether ``chunk``, projected but not read by the predicate, is
        decoded after the selection: its codes are compacted with the
        other projected columns and only the kept codes are decoded.  A
        backend says True only for a DICT or DICTP chunk, and only where
        that never does more work than decoding every row first; this
        one never defers."""
        return False

    def scan_row_group(self, src, meta, rg,
                       columns: Sequence[str] | None = None,
                       predicate: Expr | None = None,
                       report: dict | None = None) -> Table:
        """Decode + filter + project one row group (the scan_op payload).
        A projected column the predicate does not read is decoded after
        the selection, at the kept rows alone, where ``defers`` says so.
        ``report``, when given, is filled with the per-column / predicate
        routing this call actually took (kernel vs host fallback), the
        columns it deferred (``"deferred"``) and where its buffers were
        inflated (``read_chunks``)."""
        names = list(columns) if columns is not None else meta.schema.names
        read = predicate.columns() if predicate is not None else set()
        order = sorted(set(names) | read, key=meta.schema.index)
        routes: dict[str, str] = {}

        def decode(chunk: ChunkData) -> Column:
            col = self.decode_column(chunk)
            routes[chunk.field.name] = col.__dict__.pop("_decode_route",
                                                        "host")
            return col

        cols: dict[str, Column] = {}
        deferred: dict[str, ChunkData] = {}
        chunks = read_chunks(src, meta, rg, order, report)
        try:
            for c in chunks:
                n = c.field.name
                if predicate is not None and n not in read \
                        and self.defers(c):
                    deferred[n] = c
                else:
                    cols[n] = decode(c)
        finally:
            chunks.close()
        if predicate is None:
            out = Table(meta.schema.select(names), [cols[n] for n in names])
        else:
            eager = [n for n in order if n in cols]
            mask = np.asarray(self.evaluate_predicate(
                Table(meta.schema.select(eager), [cols[n] for n in eager]),
                predicate, report), "?")
            # a column the predicate alone reads is not compacted; a
            # deferred one is compacted as its codes
            proj = [cols[n] if n in cols else
                    Column(Field(n, "int32"), _dict_codes(deferred[n]),
                           deferred[n].validity()) for n in names]
            kept = self.compact(Table(Schema(tuple(c.field for c in proj)),
                                      proj), mask, report)
            out = Table(meta.schema.select(names), [
                _decode_kept(deferred[n], c, decode) if n in deferred
                else c for n, c in zip(names, kept.columns)])
        if report is not None:
            report["columns"] = {n: routes[n] for n in order}
            report["deferred"] = list(deferred)
        return out

    def describe(self, meta, rg, columns: Sequence[str] | None,
                 predicate: Expr | None) -> str:
        """Static routing summary from footer metadata alone — what
        ``explain()`` prints before any byte is read."""
        return self.name


class NumPyBackend(DecodeBackend):
    """The host decode path (exactly the code ``parquet.scan_row_group``
    used to inline)."""

    name = "numpy"
    decode_rate_prior = 150e6    # matches the paper-testbed Xeon prior

    def decode_column(self, chunk: ChunkData) -> Column:
        return Column(chunk.field, _host_decode(chunk), chunk.validity())

    def evaluate_predicate(self, tbl, predicate, report=None):
        if report is not None:
            report["predicate"] = "host"
        with span("repro.decode.host"):
            return predicate.evaluate(tbl)

    def compact(self, tbl, mask, report=None):
        if report is not None:
            report["compact"] = "host"
        with span("repro.decode.host"):
            return tbl.filter(mask)


# ---------------------------------------------------------------------------
# Pallas backend
# ---------------------------------------------------------------------------


def _fetch(result) -> np.ndarray:
    """A kernel's result on the host: the blocking read from the device,
    or the array itself where the kernel's entry already made that read
    (64-bit results, widened on the host)."""
    if isinstance(result, np.ndarray):
        return result
    with span("repro.kernel.fetch"):
        return np.asarray(result)


def _f32_exact_values(values: np.ndarray) -> bool:
    """True when every value survives the kernels' f32 compute exactly."""
    if values.dtype.kind == "b":
        return True
    if values.dtype == np.float32:
        return True
    if values.dtype.kind in "iu":
        return len(values) == 0 or \
            int(np.abs(values).max()) < F32_EXACT
    return False


def _f32_exact_scalar(v) -> bool:
    """A comparison constant the kernel can hold exactly in f32."""
    if isinstance(v, bool) or isinstance(v, np.bool_):
        return True
    if not isinstance(v, (int, float, np.integer, np.floating)):
        return False
    f = float(v)
    return np.isfinite(f) and float(np.float32(f)) == f


def _kernel_dictionary(chunk: ChunkData) -> np.ndarray | None:
    """The dictionary of a chunk that the gather kernel decodes: DICT or
    DICTP, of a kernel type, every entry f32-exact (an int dictionary
    outside that domain is the host-fallback condition); None for any
    other chunk."""
    if (chunk.encoding not in (encodings.DICT, encodings.DICTP)
            or not _kernel_type(chunk.field)):
        return None
    dic = np.frombuffer(chunk.data_bufs[1], chunk.field.numpy_dtype)
    return dic if _f32_exact_values(dic) else None


def _flatten(pred: Expr):
    """Flatten an expression into (leaves, combine, negate) when it is a
    flat AND- or OR-tree of Cmp leaves (optionally under one Not); None
    when any other node type (IsIn / Bloom / mixed logic) appears."""
    negate = False
    if isinstance(pred, Not):
        pred, negate = pred.expr, True
    stack, leaves, kinds = [pred], [], set()
    while stack:
        node = stack.pop()
        if isinstance(node, Cmp):
            leaves.append(node)
        elif isinstance(node, (And, Or)):
            kinds.add("and" if isinstance(node, And) else "or")
            stack += [node.lhs, node.rhs]
        else:
            return None
    if len(kinds) > 1:
        return None
    return leaves, (kinds.pop() if kinds else "and"), negate


class PallasBackend(DecodeBackend):
    """The accelerator decode path (``repro.kernels``), with per-column /
    per-predicate host fallback for everything the kernels cannot express
    exactly.  Safe to share across scan threads: it holds no per-call
    state (kernel jit caches are process-global)."""

    name = "pallas"
    # A hand-set prior, ten times the host's, not a measured rate: on a
    # TPU v5e the dictionary decode reaches 5.76% (taxi cell) and 0.24%
    # (TPC-H Q6 cell) of its HBM roofline.  The EWMA corrects from there.
    decode_rate_prior = 1.5e9

    def defers(self, chunk: ChunkData) -> bool:
        # exactly the kernel route: a gather of the kept codes is never
        # more work than a gather of every row
        return _kernel_dictionary(chunk) is not None

    def decode_column(self, chunk: ChunkData) -> Column:
        dic = _kernel_dictionary(chunk)
        if dic is None:
            values, route = _host_decode(chunk), "host"
        else:
            from repro.kernels import decode_dictionary

            # a kernel error propagates
            with span("repro.kernel.dict_decode"):
                values = _fetch(decode_dictionary(_dict_codes(chunk), dic))
            route = "kernel"
        col = Column(chunk.field, values, chunk.validity())
        col._decode_route = route        # scraped into the scan report
        return col

    # -- predicate ---------------------------------------------------------
    def _lower(self, tbl: Table, predicate: Expr):
        """(kernel Program, referenced Columns) or (None, reason)."""
        flat = _flatten(predicate)
        if flat is None:
            return None, "unsupported-node"
        leaves, combine, negate = flat
        cols: list[Column] = []
        col_idx: dict[str, int] = {}
        terms = []
        for leaf in leaves:
            col = tbl.column(leaf.column)
            if not _kernel_type(col.field):
                return None, f"{leaf.column}:{col.field.type}"
            value = to_physical(col.field.type, leaf.value)
            if not _f32_exact_scalar(value):
                return None, f"{leaf.column}:value"
            if not _f32_exact_values(col.values):
                return None, f"{leaf.column}:f32-domain"
            if col.validity is not None and (combine != "and" or negate):
                # nulls distribute over AND (mask & every validity) but
                # not over OR / NOT — those mixes stay on the host
                return None, f"{leaf.column}:validity"
            if leaf.column not in col_idx:
                col_idx[leaf.column] = len(cols)
                cols.append(col)
            terms.append((col_idx[leaf.column], _KERNEL_OPS[leaf.op],
                          float(value)))
        from repro.kernels import build_program

        return (build_program(terms, combine, negate), cols), None

    def evaluate_predicate(self, tbl, predicate, report=None):
        lowered, reason = self._lower(tbl, predicate)
        if lowered is None:
            if report is not None:
                report["predicate"] = f"host:{reason}"
            with span("repro.decode.host"):
                return predicate.evaluate(tbl)
        from repro.kernels import fused_predicate

        prog, cols = lowered
        with span("repro.kernel.predicate"):
            mask = _fetch(fused_predicate(
                [np.asarray(c.values, np.float32) for c in cols], prog))
        for c in cols:
            if c.validity is not None:     # AND-combine only (see _lower)
                mask = mask & c.validity
        if report is not None:
            report["predicate"] = "kernel"
        return mask

    # -- selection ---------------------------------------------------------
    def compact(self, tbl, mask, report=None):
        from repro.kernels import pack_tokens

        idx = np.flatnonzero(mask)
        n_sel = len(idx)
        # the kernel is jitted per (n, capacity) shape: exact capacities
        # would retrace on every new selectivity
        capacity = _bucket(n_sel)
        routes = {}
        out_cols = []
        for c in tbl.columns:
            if (capacity and _kernel_type(c.field)
                    and _f32_exact_values(c.values)):
                with span("repro.kernel.pack"):
                    packed, _ = pack_tokens(c.values, mask, capacity)
                    values = _fetch(packed)[:n_sel]
                validity = None if c.validity is None else c.validity[idx]
                out_cols.append(Column(c.field, values, validity))
                routes[c.field.name] = "kernel"
            else:
                with span("repro.decode.host"):
                    out_cols.append(c.take(idx))
                routes[c.field.name] = "host"
        if report is not None:
            report["compact"] = routes
        return Table(tbl.schema, out_cols)

    # -- explain -----------------------------------------------------------
    def describe(self, meta, rg, columns, predicate):
        """Per-column routing from footer metadata (encoding, dtype, and
        min/max stats for the int f32-domain check); the live scan makes
        the same calls against the actual buffers."""
        names = list(columns) if columns is not None else meta.schema.names
        needed = set(names)
        if predicate is not None:
            needed |= predicate.columns()
        kernel, host = [], []
        for n in sorted(needed, key=meta.schema.index):
            field = meta.schema.field(n)
            chunk = rg.chunks[meta.schema.index(n)]
            ok = (chunk.encoding in (encodings.DICT, encodings.DICTP)
                  and _kernel_type(field))
            if ok and field.physical != "float32":
                st = chunk.stats
                ok = (st.min is not None
                      and max(abs(int(st.min)), abs(int(st.max)))
                      < F32_EXACT)
            (kernel if ok else host).append(
                n if ok else f"{n}({chunk.encoding})")
        pred = ""
        if predicate is not None:
            pred = " pred=fused" if _flatten(predicate) is not None \
                else " pred=host"
        detail = "; ".join(p for p in (
            f"kernel={','.join(kernel)}" if kernel else "",
            f"host={','.join(host)}" if host else "") if p)
        return f"pallas[{detail}]{pred}"


# ---------------------------------------------------------------------------
# Resolution
# ---------------------------------------------------------------------------

_BACKENDS: dict[str, DecodeBackend] = {}


def resolve_backend(backend: "DecodeBackend | str | None") -> DecodeBackend:
    """Resolve a ``decode_backend=`` argument: None -> the NumPy host
    path, a known name ("numpy" / "pallas") -> a shared instance (so
    kernel jit caches are reused), an instance passes through."""
    if isinstance(backend, DecodeBackend):
        return backend
    if backend is None:
        backend = "numpy"
    if isinstance(backend, str):
        inst = _BACKENDS.get(backend)
        if inst is None:
            if backend == "numpy":
                inst = _BACKENDS.setdefault("numpy", NumPyBackend())
            elif backend == "pallas":
                inst = _BACKENDS.setdefault("pallas", PallasBackend())
        if inst is not None:
            return inst
    raise ValueError(
        f"unknown decode backend {backend!r}: pass 'numpy', 'pallas', or "
        "a DecodeBackend instance")
