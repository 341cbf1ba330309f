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

The scheduler prices the two regimes separately: each backend carries a
``decode_rate_prior`` (stored bytes per second of decode+filter) that
seeds the client-side EWMA in ``repro.dataset.scheduler``.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

from repro.aformat import compression, encodings
from repro.aformat.expressions import And, Cmp, Expr, Not, Or
from repro.aformat.schema import Field
from repro.aformat.table import Column, Table

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


def read_chunk(src, meta, rg, name: str) -> ChunkData:
    """Read + decompress one column chunk (``meta``/``rg`` are the
    ``parquet.FileMeta``/``RowGroupMeta`` footer objects, duck-typed so
    this module never imports the file format)."""
    field = meta.schema.field(name)
    chunk = rg.chunks[meta.schema.index(name)]
    bufs = []
    off = chunk.offset
    for ln in chunk.buffer_lengths:
        bufs.append(compression.decompress(chunk.codec, src.read(off, ln)))
        off += ln
    return ChunkData(field, chunk.encoding, bufs, rg.num_rows)


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

    def scan_row_group(self, src, meta, rg,
                       columns: Sequence[str] | None = None,
                       predicate: Expr | None = None,
                       report: dict | None = None) -> Table:
        """Decode + filter + project one row group (the scan_op payload).
        ``report``, when given, is filled with the per-column / predicate
        routing this call actually took (kernel vs host fallback)."""
        names = list(columns) if columns is not None else meta.schema.names
        needed = set(names)
        if predicate is not None:
            needed |= predicate.columns()
        order = sorted(needed, key=meta.schema.index)
        cols = {n: self.decode_column(read_chunk(src, meta, rg, n))
                for n in order}
        if report is not None:
            report["columns"] = {n: getattr(cols[n], "_decode_route",
                                            "host") for n in order}
            for n in order:
                if hasattr(cols[n], "_decode_route"):
                    del cols[n]._decode_route
        tbl = Table(meta.schema.select(order), [cols[n] for n in order])
        if predicate is not None:
            mask = np.asarray(self.evaluate_predicate(tbl, predicate,
                                                      report), "?")
            tbl = self.compact(tbl, mask, report)
        return tbl.select(names)

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
        values = encodings.decode(chunk.field.type, chunk.encoding,
                                  chunk.data_bufs, chunk.num_rows,
                                  chunk.field.numpy_dtype)
        return Column(chunk.field, values, chunk.validity())

    def evaluate_predicate(self, tbl, predicate, report=None):
        if report is not None:
            report["predicate"] = "host"
        return predicate.evaluate(tbl)

    def compact(self, tbl, mask, report=None):
        if report is not None:
            report["compact"] = "host"
        return tbl.filter(mask)


# ---------------------------------------------------------------------------
# Pallas backend
# ---------------------------------------------------------------------------


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
    # Dictionary gather / fused compare are HBM-bandwidth bound on the
    # accelerator (see benchmarks/kernel_bench.py rooflines): ~an order
    # of magnitude over the host prior.  The EWMA corrects from there.
    decode_rate_prior = 1.5e9

    def decode_column(self, chunk: ChunkData) -> Column:
        route = "host"
        values = None
        if (chunk.encoding in (encodings.DICT, encodings.DICTP)
                and chunk.field.type in ("int32", "int64", "float32")):
            from repro.kernels import decode_dictionary

            if chunk.encoding == encodings.DICT:
                codes = np.frombuffer(chunk.data_bufs[0],
                                      np.int32)[:chunk.num_rows]
            else:
                # DICTP: width-bit unpack is a byte-stream transform
                # (host), the gather itself still runs on the kernel
                buf = chunk.data_bufs[0]
                codes = encodings.unpack_width(
                    buf[1:], chunk.num_rows, buf[0]).astype(np.int32)
            dic = np.frombuffer(chunk.data_bufs[1],
                                chunk.field.numpy_dtype)
            # an int dictionary outside the f32-exact domain is the
            # host-fallback condition; a kernel error propagates
            if _f32_exact_values(dic):
                values = np.asarray(decode_dictionary(codes, dic))
                route = "kernel"
        if values is None:
            values = encodings.decode(chunk.field.type, chunk.encoding,
                                      chunk.data_bufs, chunk.num_rows,
                                      chunk.field.numpy_dtype)
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
            if col.field.type not in _KERNEL_TYPES:
                return None, f"{leaf.column}:{col.field.type}"
            if not _f32_exact_scalar(leaf.value):
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
                          float(leaf.value)))
        from repro.kernels import build_program

        return (build_program(terms, combine, negate), cols), None

    def evaluate_predicate(self, tbl, predicate, report=None):
        lowered, reason = self._lower(tbl, predicate)
        if lowered is None:
            if report is not None:
                report["predicate"] = f"host:{reason}"
            return predicate.evaluate(tbl)
        from repro.kernels import fused_predicate

        prog, cols = lowered
        mask = np.asarray(fused_predicate(
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
        # round the pack capacity up to a power of two: the kernel is
        # jitted per (n, capacity) shape, so exact capacities would
        # retrace on every new selectivity — bucketing keeps the trace
        # cache hot and the [:n_sel] slice restores the exact result
        capacity = 1 << (n_sel - 1).bit_length() if n_sel else 0
        routes = {}
        out_cols = []
        for c in tbl.columns:
            if (capacity and c.field.type in _KERNEL_TYPES
                    and _f32_exact_values(c.values)):
                packed, _ = pack_tokens(c.values, mask, capacity)
                validity = None if c.validity is None else c.validity[idx]
                out_cols.append(Column(c.field,
                                       np.asarray(packed)[:n_sel],
                                       validity))
                routes[c.field.name] = "kernel"
            else:
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
                  and field.type in ("int32", "int64", "float32"))
            if ok and field.type != "float32":
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
