"""Plain reference of TPC-H Q6, "Forecasting Revenue Change" (Clause 2.4.6),
over one object of ``LINEITEM``.

From the generator's stored values (dates in days, decimals unscaled at
scale 2), in NumPy alone::

    SELECT sum(l_extendedprice * l_discount) AS revenue
    FROM lineitem
    WHERE l_shipdate >= DATE AND l_shipdate < DATE + 1 year
      AND l_discount BETWEEN DISCOUNT - 0.01 AND DISCOUNT + 0.01
      AND l_quantity < QUANTITY

The answer is one row of one ``decimal64(18,4)`` column, the sum of the
unscaled products exact in int64 at scale 4.  The control computes the
same sum in float32, the kernels' compute type, and rounds it back to
scale 4: a decode plane that summed through float32 would give it.
"""

from __future__ import annotations

import datetime
import decimal

import numpy as np

NAME = "sum_l_extendedprice*l_discount"
TYPE = "decimal64(18,4)"
COLUMNS = ["l_quantity", "l_extendedprice", "l_discount", "l_shipdate"]
EPOCH = datetime.date(1970, 1, 1)


def bounds(params: dict) -> dict:
    """The predicate's stored bounds: [ship_lo, ship_hi) in days,
    [disc_lo, disc_hi] and quantity below ``qty_hi``, in cents."""
    d = datetime.date.fromisoformat(params["date"])
    disc = int(decimal.Decimal(params["discount"]) * 100)
    return {"ship_lo": (d - EPOCH).days,
            "ship_hi": (d.replace(year=d.year + 1) - EPOCH).days,
            "disc_lo": disc - 1, "disc_hi": disc + 1,
            "qty_hi": int(params["quantity"]) * 100}


def mask(cols: dict[str, np.ndarray], params: dict) -> np.ndarray:
    b = bounds(params)
    ship, disc = cols["l_shipdate"], cols["l_discount"]
    return ((ship >= b["ship_lo"]) & (ship < b["ship_hi"])
            & (disc >= b["disc_lo"]) & (disc <= b["disc_hi"])
            & (cols["l_quantity"] < b["qty_hi"]))


def revenue(cols: dict[str, np.ndarray], params: dict) -> int:
    """The exact answer, unscaled at scale 4."""
    m = mask(cols, params)
    prod = cols["l_extendedprice"][m].astype(np.int64) \
        * cols["l_discount"][m].astype(np.int64)
    return int(prod.sum(dtype=np.int64))


def answer(cols: dict[str, np.ndarray], params: dict) -> dict:
    return {NAME: np.asarray([revenue(cols, params)], np.int64)}


def control_answer(cols: dict[str, np.ndarray], params: dict) -> dict:
    m = mask(cols, params)
    prod = cols["l_extendedprice"][m].astype(np.float32) \
        * cols["l_discount"][m].astype(np.float32)
    total = prod.sum(dtype=np.float32)
    return {NAME: np.asarray([round(float(total))], np.int64)}


def differs(got: list[tuple[str, str, np.ndarray, object]],
            want: dict[str, np.ndarray]) -> bool:
    """True unless ``got`` (name, type, values, validity) per column is
    ``want``: the same names, the type ``decimal64(18,4)``, one row, no
    null, and the same bytes."""
    if [g[0] for g in got] != list(want):
        return True
    for name, type_, values, validity in got:
        w = want[name]
        v = np.asarray(values)
        if (type_ != TYPE or validity is not None or v.dtype != w.dtype
                or v.shape != (1,) or v.tobytes() != w.tobytes()):
            return True
    return False
