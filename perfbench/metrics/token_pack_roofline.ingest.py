"""Roofline share of selection compaction (``kernels/token_pack``, module
``jit__pack``) in an ingest cell, counted as in the scan cells."""

from perfbench import readers, roofline


def read(r):
    return readers.kernel_roofline_pct(r, "_pack", "pack_calls",
                                       roofline.pack_bytes)
