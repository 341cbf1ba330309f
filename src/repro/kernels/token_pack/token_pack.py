"""Masked stream compaction (token packing) — Pallas TPU kernel.

Arrow's CPU ``Filter`` kernel emits a variable-length output — impossible
on TPU, where every shape is static.  The TPU-idiomatic equivalent returns
(fixed-capacity packed buffer, valid count).  Strategy:

  per tile (in-kernel, this file):
    pos     = exclusive-cumsum(mask)            # MXU: mask @ strict-upper
    sel     = (pos[i] == j) & mask[i]           # (TILE, TILE) selection mx
    packed  = bytes(values) @ sel^T             # MXU matmul compaction

  across tiles (ops.py epilogue, plain XLA):
    per-tile counts are a reduction of the mask, their exclusive cumsum
    each tile's output base; each tile's shift (t*TILE - base) is
    scattered to its base and a running maximum spreads it over the
    tile's slots, so one take of the packed buffers fills every slot —
    no per-slot search.

The matmul trick turns data-dependent scatter (which the MXU cannot do)
into a dense systolic op.  Values travel as the four byte planes of
their 32-bit patterns (integers < 256, exact in bf16, one non-zero term
per output), so the compaction is bit-exact at any MXU precision.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dict_decode.dict_decode import (PLANES, byte_planes,
                                                   join_bytes)

TILE = 512    # (TILE x TILE) selection matrix = 512 KiB of bf16


def _kernel(bits_ref, mask_ref, packed_ref):
    m = mask_ref[...]                                    # (1, TILE) int32
    row = jax.lax.broadcasted_iota(jnp.int32, (TILE, TILE), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (TILE, TILE), 1)
    strict_upper = (row < col).astype(jnp.bfloat16)
    pos = jnp.dot(m.astype(jnp.bfloat16), strict_upper,
                  preferred_element_type=jnp.float32).astype(jnp.int32)
    # sel[j, i]: source lane i lands in output slot j
    sel = ((row == pos) & (m == 1)).astype(jnp.bfloat16)
    planes = byte_planes(bits_ref[...], PLANES)          # (PLANES, TILE)
    packed = jax.lax.dot_general(planes, sel, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    packed_ref[...] = join_bytes(packed)


@functools.partial(jax.jit, static_argnames=("interpret",))
def tile_pack(bits: jax.Array, mask: jax.Array, *,
              interpret: bool = False) -> jax.Array:
    """bits (N,) int32, mask (N,) int32 0/1 -> (N//TILE, TILE) int32
    per-tile packed bits.  N must be a multiple of TILE."""
    n, = bits.shape
    if n % TILE:
        raise ValueError(f"N={n} not a multiple of {TILE}; pad in ops.py")
    spec = pl.BlockSpec((1, TILE), lambda i: (0, i))
    packed = pl.pallas_call(
        _kernel,
        grid=(n // TILE,),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        interpret=interpret,
        name="token_pack",
    )(bits.reshape(1, n), mask.reshape(1, n))
    return packed.reshape(n // TILE, TILE)
