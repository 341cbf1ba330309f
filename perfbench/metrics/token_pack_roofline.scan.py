"""Roofline share of selection compaction (``kernels/token_pack``, module
``jit__pack``) in an object-scan cell: values and mask read, kept values
written, at the HBM peak over the module's device time."""

from perfbench import readers, roofline


def read(r):
    return readers.kernel_roofline_pct(r, "_pack", "pack_calls",
                                       roofline.pack_bytes)
