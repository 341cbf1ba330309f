"""The decode plane's Pallas backend, with the benchmark's counters.

While ``recording`` is on it keeps every row group's routing report
(kernel or host, per column, for the predicate and per compacted
column) and the shapes of each kernel call, from which
``perfbench/roofline.py`` counts the bytes the algorithm moves.  It
decodes exactly as the backend it extends.
"""

from __future__ import annotations

import threading

import numpy as np

from repro.aformat.decode import PallasBackend


class RecordingBackend(PallasBackend):

    def __init__(self):
        self._lock = threading.Lock()
        self.recording = False
        self.reports: list[dict] = []
        self.decode_calls: list[tuple[int, int]] = []   # (rows, dict size)
        self.pack_calls: list[tuple[int, int]] = []     # (rows, kept)

    def decode_column(self, chunk):
        col = super().decode_column(chunk)
        if self.recording and getattr(col, "_decode_route", "") == "kernel":
            d = len(chunk.data_bufs[1]) // chunk.field.numpy_dtype.itemsize
            with self._lock:
                self.decode_calls.append((chunk.num_rows, d))
        return col

    def compact(self, tbl, mask, report=None):
        report = {} if report is None else report
        out = super().compact(tbl, mask, report)
        if self.recording:
            kernel = sum(r == "kernel" for r in report["compact"].values())
            call = (len(mask), int(np.count_nonzero(mask)))
            with self._lock:
                self.pack_calls += [call] * kernel
        return out

    def scan_row_group(self, src, meta, rg, columns=None, predicate=None,
                       report=None):
        report = {} if report is None else report
        out = super().scan_row_group(src, meta, rg, columns, predicate,
                                     report)
        if self.recording:
            with self._lock:
                self.reports.append(report)
        return out
