"""Client seconds of the executor's tasks (``ScanMetrics.client_cpu_s``:
decode and filter on the client, or the result's IPC decode) per million
table rows scanned in the traced window."""

from perfbench import readers


def read(r):
    c = r.counters
    return readers.per(sum(t.client_cpu_s for t in c["tasks"]),
                       c["rows_scanned"], 1e6)
