"""A traffic mix's predicate: an AND of column-against-constant terms.

In a mix a term is ``{"column", "op", "value"}``, or ``{"column", "op",
"quantile"}`` for a threshold at that quantile of the whole column, held
in the column's own type so that it compares exactly.
"""

from __future__ import annotations

from typing import Callable

import numpy as np

OPS = {"<": np.less, "<=": np.less_equal, ">": np.greater,
       ">=": np.greater_equal, "==": np.equal, "!=": np.not_equal}


def resolve(spec: dict, column: Callable[[str], np.ndarray]
            ) -> list[tuple[str, str, float]]:
    """(column, op, constant) terms; ``column(name)`` gives a column's
    values over the whole table, read only for quantile thresholds."""
    terms = []
    for t in spec["and"]:
        if t["op"] not in OPS:
            raise ValueError(f"unknown op in predicate term {t}")
        if "quantile" in t:
            vals = column(t["column"])
            value = np.quantile(vals, t["quantile"]).astype(vals.dtype)
            terms.append((t["column"], t["op"], value.item()))
        else:
            terms.append((t["column"], t["op"], t["value"]))
    return terms


def mask(terms, cols: dict[str, np.ndarray]) -> np.ndarray:
    out = np.ones(len(next(iter(cols.values()))), bool)
    for name, op, value in terms:
        out &= OPS[op](cols[name], value)
    return out
