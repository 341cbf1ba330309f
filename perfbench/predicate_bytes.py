"""The bytes the fused predicate's algorithm moves, for its roofline.

Counted from the shape of one call, as ``perfbench/roofline.py`` counts
the other kernels: a predicate over ``k`` columns of ``n`` rows reads
``k`` 32-bit columns and writes a one-byte-per-row mask, as
``roofline.pack_bytes`` counts its mask, whatever width the kernel
happens to write it in.
"""


def predicate_bytes(n: int, k: int) -> int:
    """Mask of ``n`` rows from ``k`` 32-bit columns: read the columns,
    write the mask."""
    return 4 * n * k + n
