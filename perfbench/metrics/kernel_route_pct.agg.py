"""Share of the decode plane's routing decisions (``aformat/decode.py``)
that took a kernel in an aggregate cell, over every row group of the
traced window: decoded column chunks, predicates and compacted columns."""

from perfbench import readers


def read(r):
    return readers.kernel_route_pct(r)
