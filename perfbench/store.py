"""Builds a configuration's object store from its seeded tables.

Generating and encoding the files is most of a run's set-up, so the files
are made side by side in threads (NumPy, zlib and the encoders release
the interpreter lock for most of their work) while their writes reach the
store one at a time in list order.  Inode numbers, and with them the
objects' placement over the OSDs, then follow the list and not the
threads' timing: the same seed gives the same store.
"""

from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Callable

from repro.aformat.table import Table
from repro.core import make_cluster, write_flat


class _InOrder:
    """Hands each writer a view of ``fs`` whose ``write_file`` waits for
    the writers before it in the list."""

    def __init__(self, fs):
        self.fs = fs
        self.cv = threading.Condition()
        self.turn = 0
        self.failed = False

    def view(self, i: int):
        outer = self

        class View:
            def __getattr__(self, name):
                return getattr(outer.fs, name)

            def write_file(self, *args, **kwargs):
                with outer.cv:
                    outer.cv.wait_for(lambda: outer.turn == i
                                      or outer.failed)
                    if outer.failed:
                        raise RuntimeError("an earlier file failed")
                    try:
                        return outer.fs.write_file(*args, **kwargs)
                    finally:
                        outer.turn += 1
                        outer.cv.notify_all()

        return View()

    def fail(self):
        with self.cv:
            self.failed = True
            self.cv.notify_all()


def build(config: dict, files: list[tuple[str, Callable[[], Table]]], *,
          row_group_rows: int):
    """A fresh cluster as ``config`` states it, holding ``files``: pairs of
    path and a function that makes the file's table.  Each is written in
    the flat layout with the writer's defaults for encodings."""
    fs = make_cluster(config["osds"], replication=config["replication"],
                      threads_per_osd=config["threads_per_osd"])
    order = _InOrder(fs)

    def one(i: int):
        path, make = files[i]
        try:
            write_flat(order.view(i), path, make(),
                       row_group_rows=row_group_rows,
                       codec=config["codec"],
                       build_indexes=config["build_indexes"])
        except BaseException:
            order.fail()
            raise

    workers = max(1, min(len(files), os.cpu_count() or 1))
    with ThreadPoolExecutor(workers) as pool:
        for f in [pool.submit(one, i) for i in range(len(files))]:
            f.result()
    return fs
