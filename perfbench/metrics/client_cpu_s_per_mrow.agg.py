"""Client seconds of the executor's aggregate tasks
(``TaskRecord.client_cpu_s``: the scan of the fragment and the fold of its
partial aggregate, ``repro.agg.fold``) per million table rows queried in
the traced window."""

from perfbench import readers


def read(r):
    c = r.counters
    return readers.per(sum(t.client_cpu_s for t in c["tasks"]),
                       c["rows_scanned"], 1e6)
