"""Bytes that crossed from the OSDs to the client (``TaskRecord.wire_bytes``)
per table row scanned in the traced window."""

from perfbench import readers


def read(r):
    c = r.counters
    return readers.per(sum(t.wire_bytes for t in c["tasks"]),
                       c["rows_scanned"])
