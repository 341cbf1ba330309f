"""Plain NumPy oracle for the grouped fold."""

from __future__ import annotations

import math

import numpy as np


def fold_groups_ref(keys, factors, *, sizes, terms):
    """Exact per-group sums of each monomial and the rows counted, as
    ``ops.fold_groups`` returns them, summed as Python integers."""
    n = len([*keys, *factors][0])
    gid = np.zeros(n, np.int64)
    for k, size in zip(keys, sizes):
        gid = gid * size + np.asarray(k, np.int64)
    groups = math.prod(sizes)
    sums = np.zeros((len(terms), groups), np.int64)
    for t, mono in enumerate(terms):
        vals = np.ones(n, object)
        for j in mono:
            vals = vals * np.asarray(factors[j], np.int64).astype(object)
        for g in range(groups):
            sums[t, g] = int(sum(vals[gid == g]))
    return sums, np.bincount(gid, minlength=groups).astype(np.int64)
