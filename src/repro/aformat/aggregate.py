"""Partial aggregation — the storage-side SUM/MIN/MAX/MEAN/COUNT engine.

The paper's pushdown ships *filtered columns*; an aggregate only needs a
few numbers, so shipping columns wastes exactly the wire and client CPU
the paper targets.  This module is the placement-agnostic kernel both
sides run (the same-code-at-both-placements principle of ``scan_op``):

``AggSpec``
    One aggregate: ``(op, column)`` with op in sum/min/max/mean/count
    (``column=None`` means COUNT(*)).  The column may be a measure
    (``expressions.Measure``), a tree of columns, exact literals and
    ``+ - *`` such as ``field(p) * (1 - field(d))``: its rows' values,
    exact in int64 for integer and decimal columns.

``partial_aggregate(table, specs, group_by=...)``
    Fold a decoded fragment into an :class:`AggState`, optionally grouped
    by one key column or a sequence of them (the keys of a group are then
    a tuple, in key order).  A key column may arrive as its dictionary
    codes (``Column.dictionary``): rows group on the codes and the keys
    are read from the dictionary once per group.  Storage nodes pass
    ``max_groups``: a fragment whose number of key tuples exceeds the
    bound raises :class:`CardinalityError` and the caller falls back to a
    scan (the spill-to-scan path), so a hostile key can never balloon the
    node's memory or the wire payload.  A client passes its decode
    backend, whose ``fold`` may run the fold on the accelerator.

``AggState.merge``
    Associative, commutative-up-to-float-rounding combination of partial
    states: count/sum add, min/max compare, mean carries (sum, count).
    Integer sums are carried as exact Python ints, so any merge order
    yields the same result for count/min/max/sum-of-int/mean-of-int;
    float sums can differ in the last ulp across merge orders (inherent
    to float addition, same as any parallel aggregation engine).
    Decimals are their unscaled integers: a sum of ``decimal64(p,s)`` is a
    ``decimal64(18,s)``; a measure's scale is the larger of a sum's or
    difference's two, and the sum of a product's two (``price * (1 -
    disc) * (1 + tax)`` over scale-2 columns is at scale 6), exact, and a
    sum that does not fit 18 digits raises ``OverflowError``.

``partial_from_stats``
    The zero-I/O path: ungrouped, predicate-free count/min/max are
    provable from footer statistics alone, so those fragments never touch
    storage at all.  Float min/max is excluded — footer stats skip
    non-finite values, so they cannot speak for a column that may hold
    ±inf.

``AggState.finalize(schema)``
    Produce the result Table: one row (ungrouped) or one row per group,
    sorted by key for determinism.  Empty input follows NumPy: sum=0,
    count=0, mean/min/max are null.
"""

from __future__ import annotations

import dataclasses
import decimal
import json
from typing import Any, Mapping, Sequence

import numpy as np

from repro.aformat.expressions import Measure, _FieldRef
from repro.aformat.schema import (MAX_DECIMAL_PRECISION, Field, Schema,
                                  decimal64, decimal_params, physical_type)
from repro.aformat.statistics import ColumnStats
from repro.aformat.table import Column, Table

AGG_OPS = ("sum", "min", "max", "mean", "count")

#: Default storage-side group-cardinality bound (spill-to-scan past it).
DEFAULT_MAX_GROUPS = 4096

_INT_TYPES = ("int32", "int64", "bool")


class CardinalityError(ValueError):
    """Group-by key cardinality exceeded the storage-side bound."""


def normalize_group_by(group_by) -> "str | tuple[str, ...] | None":
    """A ``group_by`` argument in its one form: None, one key's name, or
    a tuple of two or more names (a sequence of one name is that name)."""
    if group_by is None or isinstance(group_by, str):
        return group_by
    names = tuple(group_by)
    if not names or not all(isinstance(n, str) for n in names):
        raise ValueError(f"group_by takes a column name or a sequence of "
                         f"them, not {group_by!r}")
    if len(set(names)) != len(names):
        raise ValueError(f"group_by repeats a key: {names}")
    return names[0] if len(names) == 1 else names


def key_names(group_by) -> tuple[str, ...]:
    """The key columns of a ``group_by``, in key order."""
    group_by = normalize_group_by(group_by)
    if group_by is None:
        return ()
    return (group_by,) if isinstance(group_by, str) else group_by


@dataclasses.dataclass(frozen=True)
class AggSpec:
    """One aggregate: op in sum/min/max/mean/count over a column name or
    a ``Measure``; column=None => rows."""

    op: str
    column: str | Measure | None = None

    def __post_init__(self):
        if isinstance(self.column, _FieldRef):       # field("x") is "x"
            object.__setattr__(self, "column", self.column.name)
        if self.op not in AGG_OPS:
            raise ValueError(f"unsupported aggregate op {self.op!r}")
        if self.column is None and self.op != "count":
            raise ValueError(f"{self.op} requires a column")

    @property
    def name(self) -> str:
        if self.column is None:
            return "count"
        col = self.column.name if isinstance(self.column, Measure) \
            else self.column
        return f"{self.op}_{col}"

    def columns(self) -> set[str]:
        if isinstance(self.column, Measure):
            return self.column.columns()
        return {self.column} if self.column is not None else set()

    def to_json(self) -> dict:
        col = self.column.to_json() if isinstance(self.column, Measure) \
            else self.column
        return {"op": self.op, "column": col}

    @staticmethod
    def from_json(d: dict) -> "AggSpec":
        col = d.get("column")
        if isinstance(col, dict):
            col = Measure.from_json(col)
        return AggSpec(d["op"], col)


def parse_aggs(aggs) -> list[AggSpec]:
    """Normalize user input: AggSpec | (op, column) | "op(column)"."""
    out: list[AggSpec] = []
    for a in aggs:
        if isinstance(a, AggSpec):
            out.append(a)
        elif isinstance(a, str):
            if "(" in a:
                op, col = a.rstrip(")").split("(", 1)
                col = col.strip()
                out.append(AggSpec(op.strip(),
                                   None if col in ("", "*") else col))
            else:
                out.append(AggSpec(a.strip()))
        else:
            op, col = a
            out.append(AggSpec(op, col))
    return out


def needed_columns(specs: Sequence[AggSpec], group_by,
                   schema: Schema, predicate=None) -> list[str]:
    """Columns a fragment scan must decode to answer these aggregates —
    in schema order.  A pure COUNT(*) needs one column only to carry the
    row count: a predicate column if filtering, else the narrowest-by-
    position first field."""
    names = set().union(*(s.columns() for s in specs))
    names.update(key_names(group_by))
    if not names:
        if predicate is not None:
            names.add(sorted(predicate.columns())[0])
        else:
            names.add(schema.names[0])
    return sorted(names, key=schema.index)


# ---------------------------------------------------------------------------
# Partial cells: JSON-native per-aggregate accumulators
#   count -> int;  sum -> int|float;  min/max -> scalar|None (no rows);
#   mean -> [sum, count]
# ---------------------------------------------------------------------------


def _identity(spec: AggSpec):
    if spec.op == "count":
        return 0
    if spec.op == "sum":
        return 0
    if spec.op == "mean":
        return [0, 0]
    return None                       # min/max over zero rows


def _merge_cell(spec: AggSpec, a, b):
    if spec.op in ("count", "sum"):
        return a + b
    if spec.op == "mean":
        return [a[0] + b[0], a[1] + b[1]]
    if a is None:
        return b
    if b is None:
        return a
    return min(a, b) if spec.op == "min" else max(a, b)


def _py(v):
    """numpy scalar -> exact JSON-able Python scalar."""
    if isinstance(v, np.generic):
        return v.item()
    return v


def _is_int(type_name: str) -> bool:
    """Integer-valued storage: ints, bools, dates and decimals."""
    return type_name != "string" and physical_type(type_name) in _INT_TYPES


def _absmax(vals: np.ndarray) -> int:
    return max(abs(int(vals.min())), abs(int(vals.max()))) if len(vals) \
        else 0


def _int_sum(vals: np.ndarray) -> int:
    """The exact sum of integer values, as a Python int: int64 sums over
    blocks too short to overflow."""
    vals = vals.astype(np.int64, copy=False)
    block = max(1, (2 ** 63 - 1) // max(_absmax(vals), 1))
    return sum(int(np.sum(vals[i:i + block], dtype=np.int64))
               for i in range(0, len(vals), block))


def _leaf_scale(type_name: str) -> int:
    """The scale of a measure's column: a decimal's, else 0."""
    if type_name in ("string", "date32"):
        raise TypeError(f"a measure cannot compute over a {type_name} "
                        "column")
    params = decimal_params(type_name)
    return params[1] if params else 0


def _literal(value) -> tuple[int, int]:
    """(unscaled integer, scale) of a measure's literal, exactly: 1 is
    (1, 0), Decimal("0.05") is (5, 2)."""
    if isinstance(value, int):
        return value, 0
    scale = max(0, -value.as_tuple().exponent)
    num, den = value.as_integer_ratio()
    return num * 10 ** scale // den, scale


def _align(scale: int, to: int) -> int:
    """The factor that brings a value at ``scale`` to scale ``to``."""
    return 10 ** (to - scale)


def _kind(m: Measure, schema: Schema) -> tuple[str, int]:
    """("int" | "decimal" | "float", scale) of a measure over ``schema``."""
    if m.op == "field":
        t = schema.field(m.args[0]).type
        scale = _leaf_scale(t)
        kind = ("float" if "float" in physical_type(t) else
                "decimal" if decimal_params(t) else "int")
        return kind, scale
    if m.op == "lit":
        return ("decimal" if isinstance(m.args[0], decimal.Decimal)
                else "int"), _literal(m.args[0])[1]
    (ka, sa), (kb, sb) = (_kind(a, schema) for a in m.args)
    kind = next(k for k in ("float", "decimal", "int") if k in (ka, kb))
    return kind, sa + sb if m.op == "*" else max(sa, sb)


def measure_type(column, schema: Schema) -> str:
    """The type of an aggregate's column or ``Measure``: float64 where a
    float column takes part, ``decimal64(18, s)`` where a decimal does,
    else int64."""
    if not isinstance(column, Measure):
        return schema.field(column).type
    if not column.columns():
        raise TypeError(f"measure {column.name} reads no column")
    kind, scale = _kind(column, schema)
    if kind == "float":
        return "float64"
    if kind == "int":
        return "int64"
    if scale > MAX_DECIMAL_PRECISION:
        raise TypeError(f"{column.name}: scale {scale} exceeds "
                        f"{MAX_DECIMAL_PRECISION}")
    return decimal64(MAX_DECIMAL_PRECISION, scale)


def measure_terms(m: Measure, schema: Schema) -> dict[tuple[str, ...], int]:
    """An integer or decimal measure as a polynomial at its result's
    scale: {monomial (the sorted names of the columns it multiplies):
    exact integer coefficient}.  ``price * (1 - disc)`` over scale-2
    columns is {("price",): 100, ("disc", "price"): -1}.  The measure's
    sum is the coefficients' combination of the monomials' sums."""
    if m.op == "field":
        return {(m.args[0],): 1}
    if m.op == "lit":
        return {(): _literal(m.args[0])[0]}
    (a, b), (sa, sb) = ([measure_terms(x, schema) for x in m.args],
                        [_kind(x, schema)[1] for x in m.args])
    out: dict[tuple[str, ...], int] = {}
    if m.op == "*":
        for ka, ca in a.items():
            for kb, cb in b.items():
                k = tuple(sorted(ka + kb))
                out[k] = out.get(k, 0) + ca * cb
    else:
        s, sign = max(sa, sb), (1 if m.op == "+" else -1)
        for k, c in a.items():
            out[k] = out.get(k, 0) + c * _align(sa, s)
        for k, c in b.items():
            out[k] = out.get(k, 0) + sign * c * _align(sb, s)
    return {k: c for k, c in out.items() if c}


def _evaluate(m: Measure, table: Table, exact: bool):
    """(values or a Python int, scale, bound on |value|) of a measure
    over every row of ``table``: int64 where ``exact``, else float64.
    An int64 that might not hold a value raises ``OverflowError``."""
    if m.op == "field":
        col = table.column(m.args[0])
        scale = _leaf_scale(col.field.type)
        vals = col.values.astype(np.int64 if exact else np.float64,
                                 copy=False)
        return vals, scale, _absmax(vals) if exact else 0
    if m.op == "lit":
        unscaled, scale = _literal(m.args[0])
        return unscaled, scale, abs(unscaled)
    (a, sa, ba), (b, sb, bb) = (_evaluate(x, table, exact) for x in m.args)
    if m.op == "*":
        val, scale, bound = a * b, sa + sb, ba * bb
    else:
        scale = max(sa, sb)
        fa, fb = _align(sa, scale), _align(sb, scale)
        if exact and max(ba * fa, bb * fb) >= 2 ** 63:
            raise OverflowError(f"{m.name} may overflow int64")
        a, b = (a * fa if fa != 1 else a), (b * fb if fb != 1 else b)
        val, bound = (a + b if m.op == "+" else a - b), ba * fa + bb * fb
    if exact and bound >= 2 ** 63:
        raise OverflowError(f"{m.name} may overflow int64")
    return val, scale, bound


def _measure(table: Table, column) -> tuple[np.ndarray, np.ndarray | None,
                                            str]:
    """(values, validity, type) of an aggregate's column or measure over
    every row of ``table``.  An integer or decimal measure is exact in
    int64 and raises ``OverflowError`` where it might not fit."""
    if not isinstance(column, Measure):
        col = table.column(column)
        return col.values, col.validity, col.field.type
    mtype = measure_type(column, table.schema)
    exact = mtype != "float64"
    vals = _evaluate(column, table, exact)[0]
    vals = np.broadcast_to(np.asarray(vals, np.int64 if exact
                                      else np.float64), (len(table),))
    return vals, _validity(table, sorted(column.columns())), mtype


def _validity(table: Table, names) -> np.ndarray | None:
    """The rows valid in every column of ``names``; None where all are."""
    valid = None
    for name in names:
        v = table.column(name).validity
        if v is not None:
            valid = v if valid is None else valid & v
    return valid


def _sum_scalar(vals: np.ndarray, field_type: str):
    """Exact sums: integer columns accumulate into Python int (no float
    rounding, so merge order can never change the result)."""
    if len(vals) == 0:
        return 0
    if _is_int(field_type):
        return _int_sum(vals)
    return float(np.sum(vals))


def _cell_from_values(spec: AggSpec, vals: np.ndarray, field_type: str):
    """One partial cell from the *valid* values of one column."""
    if spec.op == "count":
        return int(len(vals))
    if field_type in ("string", "date32") and spec.op not in ("min",
                                                               "max"):
        raise TypeError(f"{spec.op} over {field_type} column "
                        f"{spec.column!r}")
    if spec.op == "sum":
        return _sum_scalar(vals, field_type)
    if spec.op == "mean":
        return [_sum_scalar(vals, field_type), int(len(vals))]
    if len(vals) == 0:
        return None
    if field_type == "string":
        svals = [str(v) for v in vals]
        return min(svals) if spec.op == "min" else max(svals)
    return _py(vals.min() if spec.op == "min" else vals.max())


class AggState:
    """Mergeable partial-aggregate state (the agg_op wire payload).

    Ungrouped: ``cells`` is one accumulator per spec.  Grouped:
    ``groups`` maps key -> accumulator list; the key is the key column's
    value, or the tuple of the key columns' values where ``group_by``
    names several.  ``rows`` counts the input rows folded in
    (post-predicate) — the accounting figure TaskRecords report."""

    def __init__(self, specs: Sequence[AggSpec], group_by, *,
                 cells: list | None = None,
                 groups: dict | None = None, rows: int = 0):
        self.specs = list(specs)
        self.group_by = group_by = normalize_group_by(group_by)
        if group_by is None:
            self.cells = cells if cells is not None else \
                [_identity(s) for s in self.specs]
            self.groups = None
        else:
            self.cells = None
            self.groups = groups if groups is not None else {}
        self.rows = rows

    @staticmethod
    def empty(specs: Sequence[AggSpec], group_by) -> "AggState":
        return AggState(specs, group_by)

    def merge(self, other: "AggState") -> "AggState":
        """Associative in-place combine; returns self."""
        if (len(other.specs) != len(self.specs)
                or other.group_by != self.group_by):
            raise ValueError("merging incompatible aggregate states")
        if self.group_by is None:
            self.cells = [_merge_cell(s, a, b) for s, a, b in
                          zip(self.specs, self.cells, other.cells)]
        else:
            for key, cells in other.groups.items():
                mine = self.groups.get(key)
                if mine is None:
                    self.groups[key] = list(cells)
                else:
                    self.groups[key] = [
                        _merge_cell(s, a, b)
                        for s, a, b in zip(self.specs, mine, cells)]
        self.rows += other.rows
        return self

    @property
    def num_groups(self) -> int:
        return len(self.groups) if self.groups is not None else 0

    # -- wire format ---------------------------------------------------------
    def serialize(self) -> bytes:
        body: dict = {"aggs": [s.to_json() for s in self.specs],
                      "group_by": self.group_by, "rows": self.rows}
        if self.group_by is None:
            body["cells"] = self.cells
        else:
            body["groups"] = [[k, c] for k, c in self.groups.items()]
        return json.dumps(body, separators=(",", ":")).encode()

    @staticmethod
    def deserialize(raw: bytes) -> "AggState":
        d = json.loads(raw)
        specs = [AggSpec.from_json(s) for s in d["aggs"]]
        if d["group_by"] is None:
            return AggState(specs, None, cells=d["cells"], rows=d["rows"])
        groups = {_group_key(k): c for k, c in d["groups"]}
        return AggState(specs, d["group_by"], groups=groups,
                        rows=d["rows"])

    # -- result --------------------------------------------------------------
    def finalize(self, schema: Schema) -> Table:
        """Materialize the merged state as a result Table."""
        fields = result_fields(self.specs, self.group_by, schema)
        if self.group_by is None:
            rows = [self.cells]
            keys = None
        else:
            keys = sorted(self.groups)      # deterministic output order
            rows = [self.groups[k] for k in keys]
        cols: list[Column] = []
        fi = 0
        names = key_names(self.group_by)
        for i in range(len(names)):
            cols.append(_key_column(fields[i], [
                k[i] for k in keys] if len(names) > 1 else keys))
        fi = len(names)
        for j, spec in enumerate(self.specs):
            scale = 0
            if spec.op == "mean":
                params = decimal_params(measure_type(spec.column, schema))
                scale = params[1] if params else 0
            cols.append(_agg_column(fields[fi + j],
                                    [r[j] for r in rows], spec, scale))
        return Table(Schema(tuple(fields)), cols)


def _group_key(k):
    """A group key back from JSON: a key tuple travels as a list; a
    single key's scalar (int/float/str/bool) as itself."""
    return tuple(k) if isinstance(k, list) else k


def result_fields(specs: Sequence[AggSpec], group_by,
                  schema: Schema) -> list[Field]:
    fields: list[Field] = []
    for name in key_names(group_by):
        src = schema.field(name)
        fields.append(Field(src.name, src.type))
    for s in specs:
        if s.op == "count":
            t = "int64"
        elif s.op == "mean":
            t = "float64"
        elif s.op == "sum":
            t = _sum_type(measure_type(s.column, schema))
        else:
            t = measure_type(s.column, schema)
        fields.append(Field(s.name, t, nullable=True))
    return fields


def _sum_type(type_name: str) -> str:
    params = decimal_params(type_name)
    if params is not None:
        return decimal64(MAX_DECIMAL_PRECISION, params[1])
    return "int64" if _is_int(type_name) else "float64"


def _key_column(field: Field, keys: list) -> Column:
    if field.type == "string":
        return Column(field, np.asarray(keys, object))
    return Column(field, np.asarray(keys, field.numpy_dtype))


def _agg_column(field: Field, cells: list, spec: AggSpec,
                scale: int = 0) -> Column:
    """The result column of one aggregate; ``scale`` is a mean's decimal
    scale, by which its unscaled sum is divided."""
    n = len(cells)
    if spec.op == "mean":
        vals = np.empty(n, np.float64)
        valid = np.ones(n, "?")
        for i, (s, c) in enumerate(cells):
            if c:
                vals[i] = s / c / 10 ** scale
            else:
                vals[i], valid[i] = 0.0, False
        return Column(field, vals, valid)
    params = decimal_params(field.type)
    if params is not None and any(c is not None and abs(c) >= 10 ** params[0]
                                  for c in cells):
        raise OverflowError(f"{spec.name} exceeds {field.type}")
    if spec.op in ("min", "max"):
        valid = np.asarray([c is not None for c in cells], "?")
        if field.type == "string":
            vals = np.asarray(["" if c is None else c for c in cells],
                              object)
        else:
            vals = np.asarray([0 if c is None else c for c in cells],
                              field.numpy_dtype)
        return Column(field, vals, valid)
    # count / sum: always defined (0 over zero rows, matching np.sum)
    return Column(field, np.asarray(cells, field.numpy_dtype))


# ---------------------------------------------------------------------------
# Folding a decoded table into partial state
# ---------------------------------------------------------------------------


def partial_aggregate(table: Table, specs: Sequence[AggSpec],
                      group_by=None, max_groups: int | None = None, *,
                      backend=None) -> AggState:
    """Fold one (already filtered) table into an AggState.

    ``max_groups`` bounds the number of key tuples (storage-side
    callers); exceeding it raises :class:`CardinalityError` — the
    spill-to-scan signal.  Rows with a null in any key column are
    dropped, mirroring SQL GROUP BY.  ``backend``, a client's decode
    backend, takes the fold over (``DecodeBackend.fold``), on the
    accelerator where its kernel can fold exactly."""
    if backend is not None:
        return backend.fold(table, specs, group_by)
    group_by = normalize_group_by(group_by)
    if group_by is None:
        cells = []
        for s in specs:
            if s.column is None:
                cells.append(int(len(table)))
                continue
            vals, validity, ftype = _measure(table, s.column)
            if validity is not None:
                vals = vals[validity]
            cells.append(_cell_from_values(s, vals, ftype))
        return AggState(specs, None, cells=cells, rows=len(table))

    names = key_names(group_by)
    valid = _validity(table, names)
    if valid is not None:
        table = table.filter(valid)
    inv, keys = _group_ids(table, names)
    if max_groups is not None and len(keys) > max_groups:
        raise CardinalityError(
            f"group-by {group_by!r}: {len(keys)} groups exceed the "
            f"storage-side bound of {max_groups}")
    n_groups = len(keys)
    per_spec = [_grouped_cells(table, s, inv, n_groups) for s in specs]
    groups = {keys[g]: [per_spec[j][g] for j in range(len(specs))]
              for g in range(n_groups)}
    return AggState(specs, group_by, groups=groups, rows=len(table))


def state_from_sums(specs: Sequence[AggSpec], group_by, keys: list,
                    counts: list[int], sums: list, rows: int) -> AggState:
    """The partial state of groups folded elsewhere (the decode plane's
    fold kernel): each group's key, its rows and, for each spec, the list
    of exact per-group sums (None for a count); a mean carries its sum
    and the rows.  Ungrouped, ``keys`` holds one key, None.  Groups of
    equal keys merge."""
    def cells(g):
        return [counts[g] if s.op == "count" else col[g] if s.op == "sum"
                else [col[g], counts[g]] for s, col in zip(specs, sums)]

    if normalize_group_by(group_by) is None:
        return AggState(specs, None, cells=cells(0), rows=rows)
    groups: dict = {}
    for g, key in enumerate(keys):
        mine = groups.get(key)
        groups[key] = cells(g) if mine is None else [
            _merge_cell(s, a, b) for s, a, b in zip(specs, mine, cells(g))]
    return AggState(specs, group_by, groups=groups, rows=rows)


def _key_values(col: Column) -> tuple[np.ndarray, list]:
    """(each row's index into the keys, the sorted distinct keys) of one
    key column.  A column of dictionary codes groups on its codes; its
    keys are read from the dictionary once per distinct code."""
    if col.dictionary is not None:
        codes, inv = np.unique(col.values, return_inverse=True)
        vals = col.dictionary[codes]
        if col.dictionary.dtype == object:
            vals = np.asarray([str(v) for v in vals], object)
        uniq, remap = np.unique(vals, return_inverse=True)
        return remap[inv], [_py(k) for k in uniq]
    vals = col.values
    if col.field.type == "string":
        vals = np.asarray([str(v) for v in vals], object)
    uniq, inv = np.unique(vals, return_inverse=True)
    return inv, [_py(k) for k in uniq]


def _group_ids(table: Table, names: Sequence[str]) -> tuple[np.ndarray,
                                                           list]:
    """(each row's group index, the groups' keys in sorted order): a key
    is a scalar for one key column and a tuple for several."""
    inv, keys = _key_values(table.column(names[0]))
    if len(names) == 1:
        return inv, keys
    keys = [(k,) for k in keys]
    for name in names[1:]:
        kinv, kkeys = _key_values(table.column(name))
        combined, inv = np.unique(inv.astype(np.int64) * len(kkeys) + kinv,
                                  return_inverse=True)
        keys = [keys[c // len(kkeys)] + (kkeys[c % len(kkeys)],)
                for c in combined.tolist()]
    return inv.reshape(-1), keys


def _grouped_cells(table: Table, spec: AggSpec, inv: np.ndarray,
                   n_groups: int) -> list:
    """Per-group partial cells for one aggregate over one fragment."""
    if spec.column is None:             # COUNT(*)
        return np.bincount(inv, minlength=n_groups).tolist()
    vals, validity, ftype = _measure(table, spec.column)
    ginv = inv
    if validity is not None:
        vals, ginv = vals[validity], inv[validity]
    if spec.op == "count":
        return np.bincount(ginv, minlength=n_groups).tolist()
    if ftype in ("string", "date32") and spec.op not in ("min", "max"):
        raise TypeError(f"{spec.op} over {ftype} column {spec.column!r}")
    if spec.op in ("sum", "mean"):
        if _is_int(ftype) and _absmax(vals) * len(vals) >= 2 ** 63:
            # int64 partial sums might wrap: exact sums group by group
            order = np.argsort(ginv, kind="stable")
            ends = np.searchsorted(ginv[order], np.arange(n_groups + 1))
            sums = [_int_sum(vals[order[ends[g]:ends[g + 1]]])
                    for g in range(n_groups)]
        elif _is_int(ftype):
            acc = np.zeros(n_groups, np.int64)
            np.add.at(acc, ginv, vals.astype(np.int64, copy=False))
            sums = [int(v) for v in acc]
        else:
            sums = np.bincount(ginv, weights=vals.astype(np.float64),
                               minlength=n_groups).tolist()
        if spec.op == "sum":
            return sums
        counts = np.bincount(ginv, minlength=n_groups)
        return [[s, int(c)] for s, c in zip(sums, counts)]
    # min/max: sort rows by group, slice per group (cardinality-bounded)
    order = np.argsort(ginv, kind="stable")
    sg, sv = ginv[order], vals[order]
    starts = np.searchsorted(sg, np.arange(n_groups), side="left")
    ends = np.searchsorted(sg, np.arange(n_groups), side="right")
    out = []
    for g in range(n_groups):
        if starts[g] == ends[g]:
            out.append(None)
        else:
            part = sv[starts[g]:ends[g]]
            if ftype == "string":
                svals = [str(v) for v in part]
                out.append(min(svals) if spec.op == "min" else max(svals))
            else:
                out.append(_py(part.min() if spec.op == "min"
                               else part.max()))
    return out


# ---------------------------------------------------------------------------
# Metadata-only answers from footer statistics
# ---------------------------------------------------------------------------


def stats_answerable(spec: AggSpec, schema: Schema) -> bool:
    """Can footer stats answer this aggregate exactly?  count always;
    min/max except over floats (footer stats skip non-finite values, so
    they cannot speak for a column that may hold ±inf); sum/mean never
    (stats carry no sums)."""
    if isinstance(spec.column, Measure):
        return False
    if spec.op == "count":
        return True
    if spec.op in ("min", "max"):
        return schema.field(spec.column).physical not in ("float32",
                                                          "float64")
    return False


def partial_from_stats(specs: Sequence[AggSpec],
                       stats: Mapping[str, ColumnStats], num_rows: int,
                       schema: Schema) -> "AggState | None":
    """Build a fragment's partial state from footer stats alone (the
    zero-I/O path for ungrouped, predicate-free aggregates).  Returns
    None when any spec needs real data."""
    cells: list[Any] = []
    for s in specs:
        if not stats_answerable(s, schema):
            return None
        if s.column is None:
            cells.append(int(num_rows))
            continue
        st = stats.get(s.column)
        if st is None or st.count != num_rows:
            return None                 # stats absent or partial
        if s.op == "count":
            cells.append(int(st.count - st.null_count))
        else:
            # all-null chunk: min/max stats are None, and so is the cell
            cells.append(_py(st.min if s.op == "min" else st.max))
    return AggState(specs, None, cells=cells, rows=num_rows)
