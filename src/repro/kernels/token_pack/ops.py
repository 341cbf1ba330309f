"""Public entry for token packing: pad, tile-pack, gather-merge.

``pack_tokens`` is the full TPU Filter analogue: (values, mask, capacity)
-> (packed[capacity], count).  The expensive data-dependent compaction
runs in the Pallas kernel per tile; the inter-tile merge (plain XLA) is
one gather per output slot.  Its source index comes without a search:
each tile's shift (its flat start less its output base, from the prefix
sum of the tile counts) is scattered to the slot where its output
begins, and a running maximum carries it to the tile's other slots.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.platform import interpret_mode
from repro.kernels.token_pack.token_pack import TILE, tile_pack
from repro.trace import span


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
    # Output slot j lies in the last tile t that starts at or before it,
    # at local slot j - offsets[t], so it reads flat[j + shift[t]] with
    # shift[t] = t*TILE - offsets[t].  shift never falls from one tile to
    # the next (a tile holds at most TILE kept rows), so a scatter of the
    # shifts to their tiles' starts and a running maximum give each slot
    # its tile's shift: empty tiles share their successor's start and
    # lose the maximum to it; starts at or past capacity are dropped.
    shift = jnp.arange(counts.shape[0], dtype=jnp.int32) * TILE - offsets
    shift = jax.lax.cummax(jnp.zeros(capacity, jnp.int32)
                           .at[offsets].max(shift, mode="drop"))
    j = jnp.arange(capacity, dtype=jnp.int32)
    flat = packed_tiles.reshape(-1)
    out = jnp.where(j < total, flat[j + shift], 0)
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
        with span("repro.kernel.fetch"):
            out = np.asarray(out)
    return out.astype(out_dtype), total
