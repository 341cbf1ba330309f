"""Roofline share of the fused predicate (``kernels/predicate_fused``,
module ``jit_predicate_mask``) in an aggregate cell: the columns read and
the mask written, at the HBM peak over the module's device time."""

from perfbench import readers
from perfbench.predicate_bytes import predicate_bytes


def read(r):
    return readers.kernel_roofline_pct(r, "predicate_mask",
                                       "predicate_calls", predicate_bytes)
