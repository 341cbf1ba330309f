"""Public entry for the fused predicate kernel: padding + program build.

``build_program`` translates a (restricted) repro.aformat expression — a
flat AND/OR of column-vs-constant comparisons — into the kernel's static
Program against a given column ordering.  Columns are cast to f32; the
f32-exactness domain (|int| < 2**24) covers every corpus column we emit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.platform import interpret_mode
from repro.kernels.predicate_fused.predicate_fused import (TILE, Program,
                                                           Term,
                                                           predicate_mask)


def build_program(terms: list[tuple[int, str, float]], combine: str = "and",
                  negate: bool = False) -> Program:
    return Program(tuple(Term(c, op, float(v)) for c, op, v in terms),
                   combine, negate)


def fused_predicate(cols: list[jax.Array | np.ndarray], prog: Program
                    ) -> jax.Array:
    """cols: list of (N,) arrays -> (N,) bool mask."""
    n = int(np.shape(cols[0])[0])
    pad = (-n) % TILE
    padded = [jnp.pad(jnp.asarray(c, jnp.float32), (0, pad)) for c in cols]
    mask = predicate_mask(padded, prog, interpret=interpret_mode())
    return mask[:n].astype(bool)
