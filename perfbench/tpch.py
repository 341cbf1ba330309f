"""TPC-H ``LINEITEM`` from a seed, in NumPy alone.

The rules are those of the TPC-H Standard Specification, Revision 3.0.1,
Clause 4.2.3, at the configuration's ``scale_factor`` (SF):

- ``SF * 1,500,000`` orders with sparse keys, the first 8 of every 32;
  ``O_ORDERDATE`` uniform over [STARTDATE, ENDDATE - 151 days]; each
  order has 1 to 7 lines, uniformly, numbered from 1;
- ``L_PARTKEY`` uniform over [1, SF * 200,000]; ``L_SUPPKEY`` the spec's
  formula over one of the part's four suppliers, drawn uniformly;
- ``L_QUANTITY`` uniform over [1, 50]; ``L_EXTENDEDPRICE = L_QUANTITY *
  P_RETAILPRICE``, with ``P_RETAILPRICE = (90000 + (partkey / 10 mod
  20001) + 100 * (partkey mod 1000)) / 100``; ``L_DISCOUNT`` uniform over
  [0.00, 0.10] and ``L_TAX`` over [0.00, 0.08];
- ``L_SHIPDATE`` the order date plus [1, 121] days, ``L_COMMITDATE`` plus
  [30, 90], ``L_RECEIPTDATE`` the ship date plus [1, 30];
- ``L_RETURNFLAG`` "R" or "A" at random where the receipt date is on or
  before CURRENTDATE, else "N"; ``L_LINESTATUS`` "O" where the ship date
  is after CURRENTDATE, else "F";
- ``L_SHIPINSTRUCT`` and ``L_SHIPMODE`` uniform over the spec's lists;
  ``L_COMMENT`` 10 to 43 characters of a pseudo-text pool.

Values are the stored ones: dates are days since 1970-01-01 (int32),
decimals their unscaled int64 at scale 2 (cents).  Lines are kept in
orderkey order and split into objects at order boundaries, each of at
most ``rows_per_object`` rows.  The orders come from one stream of the
seed and each object's lines from a stream of their own, so objects can
be made in any order, or side by side in threads.
"""

from __future__ import annotations

import datetime

import numpy as np

from perfbench import datagen

EPOCH = datetime.date(1970, 1, 1)
STARTDATE = datetime.date(1992, 1, 1)
CURRENTDATE = datetime.date(1995, 6, 17)
ENDDATE = datetime.date(1998, 12, 31)

INSTRUCTIONS = ["DELIVER IN PERSON", "COLLECT COD", "NONE",
                "TAKE BACK RETURN"]
MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
#: the word lists of the spec's text grammar (Clause 4.2.2.10)
WORDS = (
    "foxes ideas theodolites pinto beans instructions dependencies excuses "
    "platelets asymptotes courts dolphins multipliers sauternes warthogs "
    "frets dinos attainments somas Tiresias patterns forges braids hockey "
    "players frays warhorses dugouts notornis epitaphs pearls tithes waters "
    "orbits gifts sheaves depths sentiments decoys realms pains grouches "
    "escapades sleep wake are cajole haggle nag use boost affix detect "
    "integrate maintain nod was lose sublate solve thrash promise engage "
    "hinder print x-ray breach eat grow impress mold poach serve run "
    "dazzle snooze doze unwind kindle play hang believe doubt furious sly "
    "careful blithe quick fluffy slow quiet ruthless thin close dogged "
    "daring brave stealthy permanent enticing idle busy regular final "
    "ironic even bold silent sometimes always never furiously slyly "
    "carefully blithely quickly fluffily slowly quietly ruthlessly thinly "
    "closely doggedly daringly bravely stealthily permanently enticingly "
    "idly busily regularly finally ironically evenly boldly silently").split()
TEXT_POOL_BYTES = 1 << 20

ORDERS, LINES, TEXT = 0, 1, 2     # stream families of datagen.rng_for


def days(d: datetime.date) -> int:
    return (d - EPOCH).days


def orders(cfg: dict, seed: int) -> dict[str, np.ndarray]:
    """Every order's key, date and line count."""
    n = cfg["scale_factor"] * cfg["orders_per_sf"]
    rng = datagen.rng_for(seed, ORDERS)
    i = np.arange(n, dtype=np.int64)
    return {"orderkey": i // 8 * 32 + i % 8 + 1,
            "orderdate": rng.integers(days(STARTDATE),
                                      days(ENDDATE) - 151 + 1,
                                      n).astype(np.int32),
            "lines": rng.integers(1, 8, n)}


def object_orders(lines: np.ndarray, rows_per_object: int
                  ) -> list[tuple[int, int]]:
    """[start, end) of the orders of each object: as many whole orders as
    fit ``rows_per_object`` rows, in order."""
    cum = np.cumsum(lines)
    out, start, base = [], 0, 0
    while start < len(lines):
        end = int(np.searchsorted(cum, base + rows_per_object, "right"))
        out.append((start, end))
        start, base = end, int(cum[end - 1])
    return out


def text_pool(seed: int) -> str:
    """The pseudo-text that comments are cut from: words of the spec's
    lists drawn uniformly, separated by spaces."""
    rng = datagen.rng_for(seed, TEXT)
    words = np.asarray(WORDS, object)
    n = TEXT_POOL_BYTES // 6
    return " ".join(words[rng.integers(len(words), size=n)])


def lineitem(cfg: dict, orders_: dict[str, np.ndarray], span: tuple[int, int],
             seed: int, obj: int, pool: str) -> dict[str, np.ndarray]:
    """The lines of orders ``span`` (object ``obj``), column by column in
    the configuration's order: stored values, strings as objects."""
    lo, hi = span
    lines = orders_["lines"][lo:hi]
    n = int(lines.sum())
    rng = datagen.rng_for(seed, LINES, obj)
    first = np.repeat(np.cumsum(lines) - lines, lines)
    odate = np.repeat(orders_["orderdate"][lo:hi], lines)
    sf = cfg["scale_factor"]
    partkey = rng.integers(1, sf * 200_000 + 1, n)
    s = sf * 10_000
    supplier = rng.integers(0, 4, n)
    quantity = rng.integers(1, 51, n)
    retail = 90_000 + partkey // 10 % 20_001 + 100 * (partkey % 1000)
    ship = odate + rng.integers(1, 122, n)
    receipt = ship + rng.integers(1, 31, n)
    current = days(CURRENTDATE)
    flag = np.where(receipt <= current, rng.integers(0, 2, n), 2)
    length = rng.integers(10, 44, n)
    start = rng.integers(0, len(pool) - 43, n)
    cols = {
        "l_orderkey": np.repeat(orders_["orderkey"][lo:hi], lines),
        "l_partkey": partkey,
        "l_suppkey": (partkey + supplier * (s // 4 + (partkey - 1) // s))
        % s + 1,
        "l_linenumber": (np.arange(n) - first + 1).astype(np.int32),
        "l_quantity": quantity * 100,
        "l_extendedprice": quantity * retail,
        "l_discount": rng.integers(0, 11, n),
        "l_tax": rng.integers(0, 9, n),
        "l_returnflag": np.asarray(["R", "A", "N"], object)[flag],
        "l_linestatus": np.where(ship > current, "O", "F").astype(object),
        "l_shipdate": ship.astype(np.int32),
        "l_commitdate": (odate + rng.integers(30, 91, n)).astype(np.int32),
        "l_receiptdate": receipt.astype(np.int32),
        "l_shipinstruct": np.asarray(INSTRUCTIONS, object)[
            rng.integers(0, len(INSTRUCTIONS), n)],
        "l_shipmode": np.asarray(MODES, object)[
            rng.integers(0, len(MODES), n)],
        "l_comment": np.asarray([pool[a:a + b] for a, b in
                                 zip(start.tolist(), length.tolist())],
                                object),
    }
    return {c["name"]: cols[c["name"]] for c in cfg["columns"]}
