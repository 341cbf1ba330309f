"""Plain reference of TPC-H Q1, "Pricing Summary Report" (Clause 2.4.1),
over one object of ``LINEITEM``.

From the generator's stored values (dates in days, decimals unscaled at
scale 2), in NumPy alone::

    SELECT l_returnflag, l_linestatus,
           sum(l_quantity), sum(l_extendedprice),
           sum(l_extendedprice * (1 - l_discount)),
           sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)),
           avg(l_quantity), avg(l_extendedprice), avg(l_discount), count(*)
    FROM lineitem WHERE l_shipdate <= date '1998-12-01' - interval DELTA day
    GROUP BY l_returnflag, l_linestatus ORDER BY l_returnflag, l_linestatus

The sums are exact integers at scales 2, 2, 4 and 6 (``1 - l_discount``
is ``100 - l_discount`` in cents), the means the exact sum over the count
over ``10**2`` in float64, the count an int64; one row per key pair, in
key order.  The control folds in float32, the kernels' compute type: a
decode plane that summed through float32 would give it.
"""

from __future__ import annotations

import datetime

import numpy as np

COLUMNS = ["l_quantity", "l_extendedprice", "l_discount", "l_tax",
           "l_returnflag", "l_linestatus", "l_shipdate"]
KEYS = ["l_returnflag", "l_linestatus"]
#: result columns: name, type, and the scale of a sum or mean
RESULT = [("l_returnflag", "string", None), ("l_linestatus", "string", None),
          ("sum_l_quantity", "decimal64(18,2)", 2),
          ("sum_l_extendedprice", "decimal64(18,2)", 2),
          ("sum_l_extendedprice*(1-l_discount)", "decimal64(18,4)", 4),
          ("sum_l_extendedprice*(1-l_discount)*(1+l_tax)", "decimal64(18,6)",
           6),
          ("mean_l_quantity", "float64", 2),
          ("mean_l_extendedprice", "float64", 2),
          ("mean_l_discount", "float64", 2),
          ("count", "int64", None)]
EPOCH = datetime.date(1970, 1, 1)
END = datetime.date(1998, 12, 1)


def cutoff(params: dict) -> int:
    """The last ship date kept, in days."""
    return (END - datetime.timedelta(days=params["delta"]) - EPOCH).days


def measures(cols: dict[str, np.ndarray], m: np.ndarray) -> list:
    """The four summed measures at the kept rows, as int64."""
    qty, price = cols["l_quantity"][m], cols["l_extendedprice"][m]
    disc, tax = cols["l_discount"][m], cols["l_tax"][m]
    disc_price = price * (100 - disc)
    return [qty, price, disc_price, disc_price * (100 + tax), disc]


def _answer(cols, params, total) -> dict:
    m = cols["l_shipdate"] <= cutoff(params)
    flag = np.asarray(cols["l_returnflag"][m], str)
    status = np.asarray(cols["l_linestatus"][m], str)
    pairs = sorted(set(zip(flag.tolist(), status.tolist())))
    qty, price, disc_price, charge, disc = measures(cols, m)
    out = {name: [] for name, _, _ in RESULT}
    for f, s in pairs:
        g = (flag == f) & (status == s)
        n = int(np.count_nonzero(g))
        sums = [total(v[g]) for v in (qty, price, disc_price, charge, disc)]
        row = [f, s, *sums[:4], sums[0] / n / 100, sums[1] / n / 100,
               sums[4] / n / 100, n]
        for (name, _, _), v in zip(RESULT, row):
            out[name].append(v)
    return {name: np.asarray(out[name], object if type_ == "string" else
                             "f8" if type_ == "float64" else "i8")
            for name, type_, _ in RESULT}


def answer(cols: dict[str, np.ndarray], params: dict) -> dict:
    """The exact answer: every sum an exact integer."""
    return _answer(cols, params, lambda v: int(v.sum(dtype=np.int64)))


def control_answer(cols: dict[str, np.ndarray], params: dict) -> dict:
    """The answer with every sum folded in float32."""
    return _answer(cols, params,
                   lambda v: round(float(v.astype(np.float32).sum(
                       dtype=np.float32))))


def differs(got: list[tuple[str, str, np.ndarray, object]],
            want: dict[str, np.ndarray]) -> bool:
    """True unless ``got`` (name, type, values, validity) per column is
    ``want``: the same names and types, the same rows in the same order,
    no null, and the same bytes (the same strings for a key)."""
    if [(g[0], g[1]) for g in got] != [(n, t) for n, t, _ in RESULT]:
        return True
    for name, _, values, validity in got:
        w, v = want[name], np.asarray(values)
        if validity is not None or v.shape != w.shape:
            return True
        if w.dtype == object:
            if v.tolist() != w.tolist():
                return True
        elif v.dtype != w.dtype or v.tobytes() != w.tobytes():
            return True
    return False
