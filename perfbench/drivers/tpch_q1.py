"""Closed-loop TPC-H Q1 queries over single objects of ``LINEITEM``.

The configuration names the table's configuration (``table``), whose
generator, objects and cluster it shares, and overrides its keys; the
traffic mix gives the clients, the placement and DELTA.  As in
``tpch_agg``, each client is one executor task slot: it draws an object
uniformly from its own seeded stream, runs Q1 over it through
``Dataset.query(...).filter(...).aggregate(..., group_by=[...])
.to_table()``, which finalizes the task's partial state to a table of
one row per group, and issues the next query when that one returns.

End-to-end metrics, as in ``tpch_agg``: ``scan_rows_per_s`` and
``scan_p90_s``.  Besides ``tpch_agg``'s counters it records each fold's
report (``report["fold"]``, the route) with the row groups' reports, and
the shape of each fold the kernel ran (``fold_calls``).
"""

from __future__ import annotations

import datetime
import time
from concurrent.futures import ThreadPoolExecutor

from perfbench import harness, store, tpch
from perfbench.drivers import tpch_agg
from perfbench.references import tpch_q1 as reference
from repro.aformat.decode import PallasBackend


class FoldRecording(tpch_agg.PredicateRecording):
    """``tpch_agg``'s recording backend, which also passes a scan's group
    keys as codes and keeps each fold's report and kernel call:
    (rows, factor columns, groups, aggregates)."""

    def __init__(self):
        super().__init__()
        self.fold_calls: list[tuple[int, int, int, int]] = []

    def scan_row_group(self, src, meta, rg, columns=None, predicate=None,
                       report=None, codes=()):
        # the decode plane's own template, which takes the keys as codes
        report = {} if report is None else report
        out = PallasBackend.scan_row_group(self, src, meta, rg, columns,
                                           predicate, report, codes)
        if self.recording:
            with self._lock:
                self.reports.append(report)
        return out

    def fold(self, table, specs, group_by, report=None):
        report = {} if report is None else report
        state = super().fold(table, specs, group_by, report)
        if self.recording:
            factors = set().union(*(s.columns() for s in specs))
            with self._lock:
                self.reports.append(report)
                if report["fold"] == "kernel":
                    self.fold_calls.append((len(table), len(factors),
                                            max(report["groups"], 1),
                                            len(specs)))
        return state


def q1_filter(params: dict):
    """Q1's predicate at ``params``: ship date on or before 1998-12-01
    less DELTA days."""
    from repro.aformat.expressions import field

    return field("l_shipdate") <= (datetime.date(1998, 12, 1)
                                   - datetime.timedelta(days=params["delta"]))


def q1_aggregates():
    """Q1's eight aggregates, in its SELECT's order."""
    from repro.aformat.expressions import field

    price, disc = field("l_extendedprice"), field("l_discount")
    disc_price = price * (1 - disc)
    return [("sum", "l_quantity"), ("sum", "l_extendedprice"),
            ("sum", disc_price), ("sum", disc_price * (1 + field("l_tax"))),
            ("mean", "l_quantity"), ("mean", "l_extendedprice"),
            ("mean", "l_discount"), ("count", None)]


class Driver(tpch_agg.Driver):
    def __init__(self, cell: harness.Cell, seed: int):
        table = harness.load_json(harness.ROOT / cell.config["table"])
        self.config = {**table, **cell.config}
        self.traffic, self.seed = cell.traffic, seed
        if self.traffic["query"] != "q1":
            raise ValueError(f"no query {self.traffic['query']!r}")
        # built first: a program without measure arithmetic or several
        # group keys fails here, before the store is built
        self.aggregates = q1_aggregates()
        self.params = self.traffic["parameters"]
        self.predicate = q1_filter(self.params)
        self.backend = None
        if self.traffic.get("decode_backend") == "pallas":
            self.backend = FoldRecording()
        self.scans = []
        self.rows_scanned = 0

    # -- set-up -------------------------------------------------------------
    def setup(self):
        from repro.aformat.schema import Field, Schema
        from repro.aformat.table import Table
        from repro.core import dataset

        cfg, seed = self.config, self.seed
        schema = Schema(tuple(Field(c["name"], c["type"])
                              for c in cfg["columns"]))
        t0 = time.perf_counter()
        orders = tpch.orders(cfg, seed)
        spans = tpch.object_orders(orders["lines"], cfg["rows_per_object"])
        pool = tpch.text_pool(seed)
        n_obj = len(spans)
        self.data: list[dict] = [{}] * n_obj
        self.rows = [0] * n_obj

        def make(i):
            def table():
                cols = tpch.lineitem(cfg, orders, spans[i], seed, i, pool)
                self.data[i] = {k: cols[k] for k in reference.COLUMNS}
                self.rows[i] = len(cols["l_orderkey"])
                return Table.from_pydict(cols, schema)
            return table

        self.fs = store.build(
            cfg, [(self._dir(i) + "/part.arw", make(i))
                  for i in range(n_obj)],
            row_group_rows=cfg["rows_per_object"])
        self.datasets = [dataset(self.fs, self._dir(i)) for i in range(n_obj)]
        t1 = time.perf_counter()
        # every shape the window uses: each object queried once, by as
        # many clients at a time as the window runs
        with ThreadPoolExecutor(self.traffic["clients"]) as ex:
            for t in [ex.submit(self._query, i) for i in range(n_obj)]:
                t.result()
        cut = reference.cutoff(self.params)
        kept = sum(int((d["l_shipdate"] <= cut).sum()) for d in self.data)
        self.notes = {"store_s": t1 - t0, "warm_s": time.perf_counter() - t1,
                      "objects": n_obj, "rows": sum(self.rows),
                      "kept_pct": 100.0 * kept / max(sum(self.rows), 1)}

    def _query(self, obj: int):
        q = self.datasets[obj].query(
            format=self.traffic["format"],
            decode_backend=self.backend).filter(self.predicate) \
            .aggregate(self.aggregates, group_by=reference.KEYS)
        return q.to_table(), q.metrics

    # -- per-layer counters ---------------------------------------------------
    def counters(self) -> dict:
        out = super().counters()
        out["fold_calls"] = list(self.backend.fold_calls) \
            if self.backend else []
        return out

    # -- correctness ----------------------------------------------------------
    def check(self, control: bool = False) -> list[harness.Check]:
        """Every object query of the window against the reference's answer
        for its object; with ``control``, against the control's answer."""
        answer = reference.control_answer if control else reference.answer
        want = {}
        differing = failed = 0
        for s in self.scans:
            if s.error:
                failed += 1
                continue
            if s.obj not in want:
                want[s.obj] = answer(self.data[s.obj], self.params)
            got = [(c.field.name, c.field.type, c.values, c.validity)
                   for c in s.table.columns]
            differing += reference.differs(got, want[s.obj])
        self.failed = failed + differing
        return [harness.Check("scans_differing", differing, 0),
                harness.Check("scans_failed", failed, 0)]
