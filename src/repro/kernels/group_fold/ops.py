"""Public entry for the grouped fold: staging, padding and the limbs'
exact int64 sum."""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

from repro.kernels.group_fold.group_fold import (MAX_GROUPS, MAX_ROWS,
                                                 PLANES, TILE, group_fold)
from repro.kernels.platform import interpret_mode
from repro.trace import span


def join_limbs(limbs: np.ndarray, terms: int) -> np.ndarray:
    """(PLANES * (terms + 1), G) int32 limb sums -> (terms + 1, G) exact
    int64 sums: each monomial's, then the rows counted."""
    by = limbs.astype(np.int64).reshape(terms + 1, PLANES, -1)
    return sum(by[:, b] << (8 * b) for b in range(4))


def fold_groups(keys, factors, *, sizes, terms):
    """Exact per-group sums of monomials of ``factors``.

    keys: one (N,) int array of codes per key, key k in [0, sizes[k]);
    the group of a row is its codes' row-major index, one of
    prod(sizes) <= MAX_GROUPS.  factors: (N,) int arrays, every value
    below 2**24 in magnitude.  terms: tuples of factor indices, each a
    monomial whose products stay below 2**31 in magnitude.  Returns
    (sums (len(terms), G) int64, counts (G,) int64).  N pads to a power
    of two (at least TILE), so a selection's length reuses a compiled
    shape."""
    arrays = [*keys, *factors]
    n = len(arrays[0])
    groups = math.prod(sizes)
    if groups > MAX_GROUPS or n > MAX_ROWS:
        raise ValueError(f"{groups} groups or {n} rows exceed the kernel's "
                         f"{MAX_GROUPS} / {MAX_ROWS}")
    width = max(TILE, 1 << (n - 1).bit_length())
    staged = np.zeros((len(keys) + max(len(factors), 1), width), np.int32)
    for row, a in zip(staged, arrays):
        row[:n] = a
    out = group_fold(jnp.asarray(staged), jnp.int32(n), nkeys=len(keys),
                     sizes=tuple(sizes), terms=tuple(map(tuple, terms)),
                     interpret=interpret_mode())
    with span("repro.kernel.fetch"):
        limbs = np.asarray(out)
    total = join_limbs(limbs, len(terms))[:, :groups]
    return total[:-1], total[-1]
