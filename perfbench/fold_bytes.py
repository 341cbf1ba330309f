"""The bytes the grouped fold's algorithm moves, for its roofline.

Counted from the shape of one fold, as ``perfbench/roofline.py`` counts
the other kernels, whatever route folds it: ``rows`` kept rows read
``factors`` 32-bit factor columns and a one-byte group id each, and the
fold writes one 8-byte sum for each of ``groups`` groups and ``outputs``
aggregates.
"""


def fold_bytes(rows: int, factors: int, groups: int, outputs: int) -> int:
    """Per-group sums of ``rows`` rows: read the factors and the group
    ids, write the sums."""
    return 4 * rows * factors + rows + 8 * groups * outputs
