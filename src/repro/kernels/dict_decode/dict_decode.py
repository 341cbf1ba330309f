"""Dictionary decode — Pallas TPU kernel.

DICT-encoded column chunks store (codes: intK, dictionary: D values);
decoding is ``values[i] = dictionary[codes[i]]``.  Values travel as their
32-bit patterns (``int32`` bits of an f32 or int32 dictionary), so the
decode is bit-exact for every value, whatever precision the MXU uses.

Two routes, chosen statically by dictionary size:

  one-hot matmul (D <= ONEHOT_MAX)   the dictionary is split into four
       byte planes (PLANES, D) bf16 — every byte is an integer < 256,
       exact in bf16.  Each grid step (i, k) builds the one-hot block
       (DK, TILE) of dictionary slice k against code tile i and
       accumulates ``planes_k @ onehot`` in f32 (one non-zero term per
       output: exact).  The last k reassembles the bytes into int32 bits.
  XLA gather     (D  > ONEHOT_MAX)   ``jnp.take`` on the bits, outside
       Pallas: the one-hot work grows with D, a gather does not.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 2048           # codes per grid step (lanes)
DK = 512              # dictionary entries per grid step
ONEHOT_MAX = 2048     # one-hot route up to here, XLA gather above
PLANES = 8            # four byte planes, padded to a full sublane tile


def byte_planes(bits: jax.Array, rows: int) -> jax.Array:
    """(n,) int32 bit patterns -> (rows, n) bf16 with row k = byte k
    (rows 4.. are zero)."""
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, bits.shape[-1]), 0)
    b = (bits.reshape(1, -1) >> ((row & 3) * 8)) & 0xFF
    return jnp.where(row < 4, b, 0).astype(jnp.bfloat16)


def join_bytes(planes: jax.Array) -> jax.Array:
    """(rows, n) f32 holding exact bytes in rows 0-3 -> (1, n) int32."""
    b = planes.astype(jnp.int32)
    return (b[0:1] | (b[1:2] << 8) | (b[2:3] << 16) | (b[3:4] << 24))


def _kernel(codes_ref, planes_ref, out_ref, acc_ref):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dk = planes_ref.shape[1]
    ids = jax.lax.broadcasted_iota(jnp.int32, (dk, codes_ref.shape[1]), 0)
    onehot = (ids + k * dk == codes_ref[...]).astype(jnp.bfloat16)
    acc_ref[...] += jnp.dot(planes_ref[...], onehot,
                            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(1) - 1)
    def _():
        out_ref[...] = join_bytes(acc_ref[...])


def _onehot_decode(codes, bits, interpret: bool):
    n, = codes.shape
    d, = bits.shape
    dk = min(DK, d)
    return pl.pallas_call(
        _kernel,
        grid=(n // TILE, d // dk),
        in_specs=[pl.BlockSpec((1, TILE), lambda i, k: (0, i)),
                  pl.BlockSpec((PLANES, dk), lambda i, k: (0, k))],
        out_specs=pl.BlockSpec((1, TILE), lambda i, k: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((PLANES, TILE), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="dict_decode",
    )(codes.reshape(1, n), byte_planes(bits, PLANES)).reshape(n)


def padded_dict_size(d: int) -> int:
    """Dictionary length the one-hot route needs: a multiple of 128, and
    of DK once it spans more than one block."""
    step = 128 if d <= DK else DK
    return -(-d // step) * step


@functools.partial(jax.jit, static_argnames=("interpret",))
def dict_decode(codes: jax.Array, bits: jax.Array, *,
                interpret: bool = False) -> jax.Array:
    """codes (N,) int32, bits (D,) int32 -> (N,) int32 decoded bits.

    N must be a multiple of TILE; on the one-hot route D must be
    ``padded_dict_size(D)`` (ops.py pads)."""
    n, = codes.shape
    d, = bits.shape
    if n % TILE:
        raise ValueError(f"unpadded N={n}; use ops.py")
    if d > ONEHOT_MAX:
        return jnp.take(bits, codes, mode="clip")
    if d != padded_dict_size(d):
        raise ValueError(f"unpadded D={d}; use ops.py")
    return _onehot_decode(codes, bits, interpret)
