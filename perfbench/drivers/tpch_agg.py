"""Closed-loop TPC-H aggregate queries over single objects of ``LINEITEM``.

The configuration gives the table (``perfbench/tpch.py`` makes it from
the seed at the configuration's scale factor, split into objects at
order boundaries) and the cluster; the traffic mix gives the clients,
the placement (``format``, ``decode_backend``), the query and its
substitution parameters.  Each client is one executor task slot, as a
Dask or Spark task runs one query per partition: it draws an object
uniformly from its own seeded stream, runs the query over it through
``Dataset.query(...).filter(...).aggregate(...).to_table()``, which
finalizes the task's partial state to a one-row table, and issues the
next query when that one returns.

End-to-end metrics: ``scan_rows_per_s``, the ``LINEITEM`` rows of every
object query completed in the window over the window, and
``scan_p90_s``, the 90th percentile of the latency, issue to returned
``Table``, of every object query issued in the window.
"""

from __future__ import annotations

import datetime
import decimal
import threading
import time
from concurrent.futures import ThreadPoolExecutor

from jax.profiler import TraceAnnotation

from perfbench import harness, store, tpch
from perfbench.drivers.object_scan import Scan, client_objects
from perfbench.recorder import RecordingBackend
from perfbench.references import tpch_q6 as reference


class PredicateRecording(RecordingBackend):
    """The benchmark's recording backend, which also keeps the shape of
    each predicate the kernel evaluated: (rows, columns read)."""

    def __init__(self):
        super().__init__()
        self.predicate_calls: list[tuple[int, int]] = []

    def evaluate_predicate(self, tbl, predicate, report=None):
        report = {} if report is None else report
        mask = super().evaluate_predicate(tbl, predicate, report)
        if self.recording and report["predicate"] == "kernel":
            with self._lock:
                self.predicate_calls.append(
                    (len(tbl), len(predicate.columns())))
        return mask


def q6_filter(params: dict):
    """Q6's predicate at ``params``, its constants as dates and decimals."""
    from repro.aformat.expressions import field

    lo = datetime.date.fromisoformat(params["date"])
    hi = lo.replace(year=lo.year + 1)
    disc = decimal.Decimal(params["discount"])
    step = decimal.Decimal("0.01")
    ship, discount = field("l_shipdate"), field("l_discount")
    return ((ship >= lo) & (ship < hi) & (discount >= disc - step)
            & (discount <= disc + step)
            & (field("l_quantity") < decimal.Decimal(params["quantity"])))


def q6_measure():
    from repro.aformat.expressions import field

    return field("l_extendedprice") * field("l_discount")


class Driver:
    def __init__(self, cell: harness.Cell, seed: int):
        self.config, self.traffic, self.seed = cell.config, cell.traffic, seed
        if self.traffic["query"] != "q6":
            raise ValueError(f"no query {self.traffic['query']!r}")
        self.backend = None
        if self.traffic.get("decode_backend") == "pallas":
            self.backend = PredicateRecording()
        self.scans: list[Scan] = []
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
                # the reference keeps the generator's arrays it reads
                self.data[i] = {k: cols[k] for k in reference.COLUMNS}
                self.rows[i] = len(cols["l_orderkey"])
                return Table.from_pydict(cols, schema)
            return table

        self.fs = store.build(
            cfg, [(self._dir(i) + "/part.arw", make(i))
                  for i in range(n_obj)],
            row_group_rows=cfg["rows_per_object"])
        self.datasets = [dataset(self.fs, self._dir(i)) for i in range(n_obj)]
        self.params = self.traffic["parameters"]
        self.predicate = q6_filter(self.params)
        self.measure = q6_measure()
        t1 = time.perf_counter()
        # every shape the window uses: each object queried once, by as
        # many clients at a time as the window runs
        with ThreadPoolExecutor(self.traffic["clients"]) as ex:
            for t in [ex.submit(self._query, i) for i in range(n_obj)]:
                t.result()
        total = sum(reference.revenue(d, self.params) for d in self.data)
        self.notes = {"store_s": t1 - t0, "warm_s": time.perf_counter() - t1,
                      "objects": n_obj, "rows": sum(self.rows),
                      "revenue": str(decimal.Decimal(total).scaleb(-4))}

    def _dir(self, i: int) -> str:
        return f"/{self.config['name']}/{i:05d}"

    def _query(self, obj: int):
        q = self.datasets[obj].query(
            format=self.traffic["format"],
            decode_backend=self.backend).filter(self.predicate) \
            .aggregate([("sum", self.measure)])
        return q.to_table(), q.metrics

    # -- the window -----------------------------------------------------------
    def recording(self, on: bool):
        if self.backend is not None:
            self.backend.recording = on

    def window(self, seconds: float) -> dict:
        start = time.perf_counter()
        deadline = start + seconds
        per_client: list[list[Scan]] = [[] for _ in
                                        range(self.traffic["clients"])]

        def client(c: int):
            objs = client_objects(self.seed, c, len(self.datasets))
            while (t0 := time.perf_counter()) < deadline:
                s = Scan(c, next(objs), t0, t0)
                try:
                    with TraceAnnotation("bench.scan"):
                        s.table, metrics = self._query(s.obj)
                    s.tasks = list(metrics.tasks)
                except Exception as e:  # noqa: BLE001 - counted as failed
                    s.error = f"{type(e).__name__}: {e}"
                s.done = time.perf_counter()
                per_client[c].append(s)

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(len(per_client))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        self.scans = [s for scans in per_client for s in scans]
        self.rows_scanned = sum(self.rows[s.obj] for s in self.scans)
        done = [s for s in self.scans if s.done <= deadline and not s.error]
        lat = [s.done - s.issued for s in self.scans]
        self.notes.update(scans=len(self.scans), done_in_window=len(done),
                          **{f"p{q}_s": harness.percentile(lat, q)
                             for q in (50, 95, 99)})
        return {"scan_rows_per_s": harness.rate(
                    sum(self.rows[s.obj] for s in done), seconds),
                "scan_p90_s": harness.percentile(lat, 90)}

    @property
    def attempted(self) -> int:
        return len(self.scans)

    # -- per-layer counters ---------------------------------------------------
    def counters(self) -> dict:
        b = self.backend
        return {"rows_scanned": self.rows_scanned,
                "tasks": [t for s in self.scans for t in s.tasks],
                "reports": list(b.reports) if b else [],
                "decode_calls": list(b.decode_calls) if b else [],
                "pack_calls": list(b.pack_calls) if b else [],
                "predicate_calls": list(b.predicate_calls) if b else []}

    # -- correctness ----------------------------------------------------------
    def release(self):
        """Free the store and the datasets; the answers stay."""
        self.fs = self.datasets = None

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
