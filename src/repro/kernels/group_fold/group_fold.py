"""Grouped exact sums — Pallas TPU kernel.

An aggregate's fold sums measures per group: ``sum(price * disc)`` over
the rows of each (returnflag, linestatus).  Its inputs are the kept
rows' group ids and the measures' factor columns, all int32 in the
f32-exact domain; a measure is a polynomial of the factors
(``aggregate.measure_terms``), so the kernel sums monomials, the
products of factors, each exact in int32 (the caller bounds them below
2**31), and the caller combines them with their integer coefficients.

Per tile of rows (in-kernel, this file):

    v[t]      = prod of the monomial's factor rows     # VPU, int32
    planes[t] = bytes 0-2 of v[t] (0..255) and its     # (8, TILE) bf16
                signed top byte (-128..127)
    onehot    = (group ids == gid)                     # (G, TILE) bf16
    out      += planes @ onehot^T                      # MXU, f32 -> int32

Every byte and one-hot entry is an integer exact in bf16, and a tile's
sum of TILE bytes stays below 2**24, so the f32 product is exact; the
int32 accumulator over the grid holds at most 255 * MAX_ROWS < 2**31.
One more plane block of ones counts each group's rows.  The result is
each monomial's four limb sums per group: ``ops.py`` adds them as
``sum_b limb_b << 8b`` into exact int64.  Rows whose group id is -1
(padding) match no group.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 2048       # rows per grid step (lanes)
LANES = 128       # groups pad to a multiple of this
MAX_GROUPS = 256  # group ids a call takes
#: rows a call folds: a limb total is at most 255 * MAX_ROWS < 2**31
MAX_ROWS = 1 << 23
PLANES = 8        # rows of one monomial's planes: 4 bytes, 4 zero


def _kernel(gid_ref, f_ref, out_ref, *, terms):
    @pl.when(pl.program_id(0) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    rows, groups = out_ref.shape
    ids = jax.lax.broadcasted_iota(jnp.int32, (groups, TILE), 0)
    onehot = (ids == gid_ref[...]).astype(jnp.bfloat16)
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, TILE), 0)
    t, b = r // PLANES, r % PLANES
    v = jnp.ones((rows, TILE), jnp.int32)        # the count's block
    for k, mono in enumerate(terms):
        p = f_ref[mono[0]:mono[0] + 1, :]
        for j in mono[1:]:
            p = p * f_ref[j:j + 1, :]
        v = jnp.where(t == k, p, v)
    shifted = v >> (jnp.minimum(b, 3) * 8)
    planes = jnp.where(b < 3, shifted & 0xFF,
                       jnp.where(b == 3, shifted, 0)).astype(jnp.bfloat16)
    out_ref[...] += jax.lax.dot_general(
        planes, onehot, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(jnp.int32)


def padded_groups(sizes) -> int:
    """Group ids the kernel lays out for keys of ``sizes``: their
    product, padded to a multiple of LANES."""
    return -(-max(math.prod(sizes), 1) // LANES) * LANES


@functools.partial(jax.jit,
                   static_argnames=("nkeys", "sizes", "terms", "interpret"))
def group_fold(rows: jax.Array, n: jax.Array, *, nkeys: int, sizes,
               terms, interpret: bool = False) -> jax.Array:
    """rows (nkeys + K, N) int32: the key codes (key k in [0, sizes[k]))
    and then the K factor columns; ``n`` the real rows, the rest padding
    -> (PLANES * (len(terms) + 1), padded_groups(sizes)) int32 limb sums.

    The group id is the key codes' row-major index.  N must be a multiple
    of TILE and at most MAX_ROWS; each monomial's product must stay below
    2**31 (ops.py pads; the caller bounds)."""
    width = rows.shape[1]
    if width % TILE or width > MAX_ROWS:
        raise ValueError(f"N={width}: pad to a multiple of {TILE}, at most "
                         f"{MAX_ROWS}")
    gid = jnp.zeros((width,), jnp.int32)
    for k, size in enumerate(sizes):
        gid = gid * size + rows[k]
    gid = jnp.where(jnp.arange(width) < n, gid, -1)
    factors = rows[nkeys:]
    out_rows = PLANES * (len(terms) + 1)
    groups = padded_groups(sizes)
    return pl.pallas_call(
        functools.partial(_kernel, terms=terms),
        grid=(width // TILE,),
        in_specs=[pl.BlockSpec((1, TILE), lambda i: (0, i)),
                  pl.BlockSpec((factors.shape[0], TILE), lambda i: (0, i))],
        out_specs=pl.BlockSpec((out_rows, groups), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((out_rows, groups), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="group_fold",
    )(gid.reshape(1, width), factors)
