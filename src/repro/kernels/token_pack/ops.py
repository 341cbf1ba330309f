"""Public entry for token packing: pad, tile-pack, gather-merge.

``pack_tokens`` is the full TPU Filter analogue: (values, mask, capacity)
-> (packed[capacity], count).  The expensive data-dependent compaction
runs in the Pallas kernel per tile; the inter-tile merge is one gather
computed from the tile-count prefix sum (plain XLA, bandwidth-bound).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.platform import interpret_mode
from repro.kernels.token_pack.token_pack import TILE, tile_pack


@functools.partial(jax.jit, static_argnames=("capacity", "interpret"))
def _pack(bits, mask, capacity: int, interpret: bool):
    n = bits.shape[0]
    pad = (-n) % TILE
    b = jnp.pad(bits, (0, pad))
    m = jnp.pad(mask.astype(jnp.int32), (0, pad))
    packed_tiles = tile_pack(b, m, interpret=interpret)
    counts = m.reshape(-1, TILE).sum(axis=1)

    offsets = jnp.cumsum(counts) - counts            # tile -> global base
    total = jnp.minimum(jnp.sum(counts), capacity)
    # output slot j comes from tile t(j) = searchsorted(cum, j, right),
    # local slot j - offsets[t]
    j = jnp.arange(capacity, dtype=jnp.int32)
    cum = jnp.cumsum(counts)
    t = jnp.searchsorted(cum, j, side="right").astype(jnp.int32)
    t = jnp.minimum(t, counts.shape[0] - 1)
    local = j - offsets[t]
    flat = packed_tiles.reshape(-1)
    out = jnp.where(j < total, flat[t * TILE + local], 0)
    return out, total


def pack_tokens(values, mask, capacity: int):
    """values (N,), mask (N,) -> (packed (capacity,), count scalar).

    Bit-exact for 32-bit values and bools; 64-bit integers must lie in
    the f32-exact domain (< 2**24), as token ids do, and come back as
    numpy arrays of their dtype."""
    values = np.asarray(values)
    out_dtype = values.dtype
    if out_dtype.kind == "f":
        bits = values.astype(np.float32).view(np.int32)
    else:
        bits = values.astype(np.int32)
    out, total = _pack(jnp.asarray(bits), jnp.asarray(mask), capacity,
                       interpret_mode())
    if out_dtype.kind == "f":
        out = jax.lax.bitcast_convert_type(out, jnp.float32)
    if out_dtype.itemsize == 8:                     # non-canonical in jax
        return np.asarray(out).astype(out_dtype), total
    return out.astype(out_dtype), total
