"""Roofline share of the grouped fold (``kernels/group_fold``, module
``jit_group_fold``) in an aggregate cell: the factor columns and group ids
read and the sums written, at the HBM peak over the module's device time.
None on a program that records no fold on the kernel."""

from perfbench import readers
from perfbench.fold_bytes import fold_bytes


def read(r):
    return readers.kernel_roofline_pct(r, "group_fold", "fold_calls",
                                       fold_bytes)
