"""Minimal Arrow-like schema/type system.

Physical types: int32, int64, float32, float64, bool, string.  Columns are
numpy arrays (strings use object/str arrays externally; the file format
stores them Arrow-style as offsets + utf8 bytes).

Logical types, stored as a physical one (as Parquet stores them):

``date32``            days since 1970-01-01, stored as int32.
``decimal64(p,s)``    a decimal of at most ``p`` <= 18 digits, ``s`` of them
                      after the point, stored as its unscaled int64
                      (12.34 in ``decimal64(15,2)`` is 1234).

Every layer below the schema (encodings, statistics, indexes, the decode
plane's kernels) sees the physical values; ``to_physical`` turns a
``datetime.date`` or ``decimal.Decimal`` constant into one, exactly.
"""

from __future__ import annotations

import dataclasses
import datetime
import decimal
import re

import numpy as np

_TYPES = {
    "int32": np.dtype("<i4"),
    "int64": np.dtype("<i8"),
    "float32": np.dtype("<f4"),
    "float64": np.dtype("<f8"),
    "bool": np.dtype("?"),
    "string": None,  # offsets + utf8 payload
}

_DECIMAL = re.compile(r"decimal64\((\d+),(\d+)\)")
#: digits an int64 always holds
MAX_DECIMAL_PRECISION = 18
EPOCH = datetime.date(1970, 1, 1)


def decimal64(precision: int, scale: int) -> str:
    """The type name of ``decimal64(precision, scale)``."""
    return f"decimal64({precision},{scale})"


def decimal_params(type_name: str) -> tuple[int, int] | None:
    """(precision, scale) of a ``decimal64`` type name, else None."""
    m = _DECIMAL.fullmatch(type_name)
    return (int(m[1]), int(m[2])) if m else None


def physical_type(type_name: str) -> str:
    """The stored type of ``type_name``: int32 for date32, int64 for a
    decimal64, every physical type itself.  Raises on an unknown name."""
    if type_name in _TYPES:
        return type_name
    if type_name == "date32":
        return "int32"
    params = decimal_params(type_name)
    if params is None:
        raise ValueError(f"unsupported type {type_name!r}")
    p, s = params
    if not (1 <= p <= MAX_DECIMAL_PRECISION and 0 <= s <= p):
        raise ValueError(f"unsupported type {type_name!r}: decimal64 takes "
                         f"1 <= precision <= {MAX_DECIMAL_PRECISION} and "
                         "0 <= scale <= precision")
    return "int64"


def to_physical(type_name: str, value):
    """A comparison constant as the stored value of a ``type_name``
    column.  A ``datetime.date`` becomes its day number (date32 only); a
    ``decimal.Decimal`` its unscaled integer at the column's scale
    (decimal64 only), which must be exact and within the precision: a
    constant that does not convert exactly raises and is never rounded.
    Any other value is taken as a stored value already, and returned as
    it is."""
    if isinstance(value, datetime.datetime):
        raise TypeError(f"{value!r}: compare a date32 column with a "
                        "datetime.date")
    if isinstance(value, datetime.date):
        if type_name != "date32":
            raise TypeError(f"date constant {value} against a "
                            f"{type_name} column")
        return (value - EPOCH).days
    if isinstance(value, decimal.Decimal):
        params = decimal_params(type_name)
        if params is None:
            raise TypeError(f"decimal constant {value} against a "
                            f"{type_name} column")
        p, s = params
        if not value.is_finite():
            raise ValueError(f"decimal constant {value} is not finite")
        num, den = value.as_integer_ratio()
        unscaled, rest = divmod(num * 10 ** s, den)
        if rest:
            raise ValueError(f"decimal constant {value} is not exact at "
                             f"scale {s} of {type_name}")
        if abs(unscaled) >= 10 ** p:
            raise ValueError(f"decimal constant {value} exceeds the "
                             f"precision of {type_name}")
        return unscaled
    return value


@dataclasses.dataclass(frozen=True)
class Field:
    name: str
    type: str
    nullable: bool = False

    def __post_init__(self):
        physical_type(self.type)

    @property
    def physical(self) -> str:
        """The stored type (see ``physical_type``)."""
        return physical_type(self.type)

    @property
    def numpy_dtype(self):
        return _TYPES[self.physical]

    def to_json(self):
        return {"name": self.name, "type": self.type,
                "nullable": self.nullable}

    @staticmethod
    def from_json(d):
        return Field(d["name"], d["type"], d.get("nullable", False))


@dataclasses.dataclass(frozen=True)
class Schema:
    fields: tuple[Field, ...]

    def __post_init__(self):
        names = [f.name for f in self.fields]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate field names: {names}")

    def __iter__(self):
        return iter(self.fields)

    def __len__(self):
        return len(self.fields)

    @property
    def names(self):
        return [f.name for f in self.fields]

    def field(self, name: str) -> Field:
        for f in self.fields:
            if f.name == name:
                return f
        raise KeyError(name)

    def index(self, name: str) -> int:
        for i, f in enumerate(self.fields):
            if f.name == name:
                return i
        raise KeyError(name)

    def select(self, names) -> "Schema":
        return Schema(tuple(self.field(n) for n in names))

    def to_json(self):
        return {"fields": [f.to_json() for f in self.fields]}

    @staticmethod
    def from_json(d):
        return Schema(tuple(Field.from_json(f) for f in d["fields"]))


def schema(*pairs, nullable=()) -> Schema:
    """schema(("a","int64"), ("b","date32"), ("c", decimal64(15, 2)))."""
    return Schema(tuple(Field(n, t, n in nullable) for n, t in pairs))


def infer_type(arr: np.ndarray) -> str:
    if arr.dtype == np.dtype("?"):
        return "bool"
    if arr.dtype.kind in ("U", "O", "T"):
        return "string"
    for name, dt in _TYPES.items():
        if dt is not None and arr.dtype == dt:
            return name
    if arr.dtype.kind == "i":
        return "int64"
    if arr.dtype.kind == "f":
        return "float64"
    raise TypeError(f"cannot infer arrow type for dtype {arr.dtype}")
