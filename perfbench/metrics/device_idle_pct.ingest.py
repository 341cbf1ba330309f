"""Idle share of the cell's chips over the traced window of an ingest
cell, averaged over the chips; the breakdown names each chip."""

from perfbench import readers


def read(r):
    return readers.idle_pct(r)
