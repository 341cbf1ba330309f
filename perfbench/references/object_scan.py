"""Plain reference of a filtered, projected scan of one object.

The answer is the object's rows that satisfy every term, in row order,
with the projected columns' values bit for bit as generated.  The
control computes the same answer with every float column rounded to the
next precision below the one the configuration states (float64 through
float32, float32 through bfloat16): a decode plane that moved values
through the narrower type would give it.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

from perfbench.references import predicate


def answer(cols: dict[str, np.ndarray], terms, columns: list[str]
           ) -> dict[str, np.ndarray]:
    keep = predicate.mask(terms, cols)
    return {c: cols[c][keep] for c in columns}


BELOW = {np.dtype(np.float64): np.float32,
         np.dtype(np.float32): ml_dtypes.bfloat16}


def lower_precision(cols: dict[str, np.ndarray]) -> dict[str, np.ndarray]:
    return {k: v.astype(BELOW[v.dtype]).astype(v.dtype)
            if v.dtype in BELOW else v for k, v in cols.items()}


def control_answer(cols, terms, columns):
    return answer(lower_precision(cols), terms, columns)


def differs(got: list[tuple[str, np.ndarray, object]],
            want: dict[str, np.ndarray]) -> bool:
    """True unless ``got`` (name, values, validity) per column is ``want``
    bit for bit: the same names in order, dtypes, lengths and bytes, and
    no nulls."""
    if [g[0] for g in got] != list(want):
        return True
    for name, values, validity in got:
        w = want[name]
        v = np.asarray(values)
        if (validity is not None or v.dtype != w.dtype
                or v.shape != w.shape or v.tobytes() != w.tobytes()):
            return True
    return False
