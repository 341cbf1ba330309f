"""Idle share of the chip over the traced window of an object-scan cell."""

from perfbench import readers


def read(r):
    return readers.idle_pct(r)
