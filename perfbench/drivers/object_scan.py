"""Closed-loop scans of single objects of an object-store table.

The configuration gives the table (column specs, objects, rows per
object) and the cluster; the traffic mix gives the clients, the
placement (``format``, ``decode_backend``), the predicate and the
projection.  Each client is one executor task slot, as a Dask or Spark
worker runs one task per object: it draws an object uniformly from its
own seeded stream, scans it through ``Dataset.query(...).to_table()``,
and issues the next scan when that one returns.

End-to-end metrics: ``scan_rows_per_s``, the table rows of every scan
completed in the window over the window, and ``scan_p90_s``, the 90th
percentile of the latency, issue to returned ``Table``, of every scan
issued in the window.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
from jax.profiler import TraceAnnotation

from perfbench import datagen, harness, store
from perfbench.drivers import predicate_expr
from perfbench.recorder import RecordingBackend
from perfbench.references import object_scan as reference
from perfbench.references import predicate as predicate_ref

DATA, CLIENTS = 0, 1     # stream families of datagen.rng_for


def client_objects(seed: int, client: int, objects: int):
    """The endless sequence of objects one client scans."""
    rng = datagen.rng_for(seed, CLIENTS, client)
    while True:
        yield int(rng.integers(objects))


def object_columns(config: dict, seed: int, i: int) -> dict:
    n = config["rows_per_object"]
    return datagen.table_columns(config["columns"], n, seed, DATA, i)


@dataclasses.dataclass
class Scan:
    client: int
    obj: int
    issued: float
    done: float
    table: object = None       # the returned Table
    tasks: list = dataclasses.field(default_factory=list)
    error: str | None = None


class Driver:
    def __init__(self, cell: harness.Cell, seed: int):
        self.config, self.traffic, self.seed = cell.config, cell.traffic, seed
        self.backend = None
        if self.traffic.get("decode_backend") == "pallas":
            self.backend = RecordingBackend()
        self.scans: list[Scan] = []
        self.rows_scanned = 0

    # -- set-up -------------------------------------------------------------
    def setup(self):
        from repro.aformat.table import Table
        from repro.core import dataset

        cfg = self.config
        n_obj = cfg["objects"]
        cols = [None] * n_obj
        t0 = time.perf_counter()

        def make(i):
            def table():
                cols[i] = object_columns(cfg, self.seed, i)
                return Table.from_pydict(cols[i])
            return table

        self.fs = store.build(
            cfg, [(self._dir(i) + "/part.arw", make(i))
                  for i in range(n_obj)],
            row_group_rows=cfg["rows_per_object"])
        self.datasets = [dataset(self.fs, self._dir(i)) for i in range(n_obj)]
        self.columns = list(self.traffic["columns"])
        self.terms = predicate_ref.resolve(
            self.traffic["predicate"],
            lambda c: np.concatenate([o[c] for o in cols]))
        self.predicate = predicate_expr(self.terms)
        # the reference keeps the generator's arrays of what it compares
        keep = set(self.columns) | {c for c, _, _ in self.terms}
        self.data = [{k: o[k] for k in keep} for o in cols]
        del cols
        t1 = time.perf_counter()
        # every shape the window uses: each object scanned once, by as
        # many clients at a time as the window runs
        with ThreadPoolExecutor(self.traffic["clients"]) as pool:
            for t in [pool.submit(self._scan, i) for i in range(n_obj)]:
                t.result()
        self.notes = {"store_s": t1 - t0, "warm_s": time.perf_counter() - t1}

    def _dir(self, i: int) -> str:
        return f"/{self.config['name']}/{i:05d}"

    def _scan(self, obj: int):
        q = self.datasets[obj].query(
            format=self.traffic["format"],
            decode_backend=self.backend).filter(self.predicate) \
            .select(*self.columns)
        return q.to_table(), q.metrics

    # -- the window ------------------------------------------------------------
    def recording(self, on: bool):
        if self.backend is not None:
            self.backend.recording = on

    def window(self, seconds: float) -> dict:
        start = time.perf_counter()
        deadline = start + seconds
        n_obj = self.config["objects"]
        per_client: list[list[Scan]] = [[] for _ in
                                        range(self.traffic["clients"])]

        def client(c: int):
            objs = client_objects(self.seed, c, n_obj)
            while (t0 := time.perf_counter()) < deadline:
                s = Scan(c, next(objs), t0, t0)
                try:
                    with TraceAnnotation("bench.scan"):
                        s.table, metrics = self._scan(s.obj)
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
        rows = self.config["rows_per_object"]
        self.rows_scanned = rows * len(self.scans)
        done = [s for s in self.scans if s.done <= deadline and not s.error]
        lat = [s.done - s.issued for s in self.scans]
        self.notes.update(scans=len(self.scans), done_in_window=len(done),
                          **{f"p{q}_s": harness.percentile(lat, q)
                             for q in (50, 95, 99)})
        return {"scan_rows_per_s": harness.rate(rows * len(done), seconds),
                "scan_p90_s": harness.percentile(
                    [s.done - s.issued for s in self.scans], 90)}

    @property
    def attempted(self) -> int:
        return len(self.scans)

    # -- per-layer counters ----------------------------------------------------
    def counters(self) -> dict:
        b = self.backend
        return {"rows_scanned": self.rows_scanned,
                "tasks": [t for s in self.scans for t in s.tasks],
                "reports": list(b.reports) if b else [],
                "decode_calls": list(b.decode_calls) if b else [],
                "pack_calls": list(b.pack_calls) if b else []}

    # -- correctness -------------------------------------------------------------
    def release(self):
        """Free the store and the datasets; the answers stay."""
        self.fs = self.datasets = None

    def check(self, control: bool = False) -> list[harness.Check]:
        """Every scan of the window against the reference's answer for its
        object; with ``control``, against the control's answer."""
        answer = reference.control_answer if control else reference.answer
        want = {}
        differing = failed = 0
        for s in self.scans:
            if s.error:
                failed += 1
                continue
            if s.obj not in want:
                want[s.obj] = answer(self.data[s.obj], self.terms,
                                     self.columns)
            got = [(c.field.name, c.values, c.validity)
                   for c in s.table.columns]
            differing += reference.differs(got, want[s.obj])
        self.failed = failed + differing
        return [harness.Check("scans_differing", differing, 0),
                harness.Check("scans_failed", failed, 0)]

