"""Public entry for dictionary decode: padding + dtype management."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.dict_decode.dict_decode import (ONEHOT_MAX, TILE,
                                                   dict_decode,
                                                   padded_dict_size)
from repro.kernels.platform import interpret_mode


def decode_dictionary(codes, dictionary):
    """codes (N,) int, dictionary (D,) numeric -> (N,) decoded values.

    The gather is bit-exact.  Integer dictionaries must fit the f32-exact
    domain (< 2**24), the contract the other kernels hold; float64
    dictionaries decode at f32.  64-bit requests come back as numpy
    arrays of the original dtype (jax canonicalizes to 32 bits
    on-device; the exactness domain makes the widening lossless).
    """
    out_dtype = np.dtype(getattr(dictionary, "dtype", np.float32))
    dic = np.asarray(dictionary)
    if out_dtype.kind in "iu":
        if np.abs(dic).max(initial=0) >= 2 ** 24:
            raise ValueError("int dictionary exceeds f32-exact domain")
        bits = dic.astype(np.int32)
    else:
        bits = dic.astype(np.float32).view(np.int32)
    codes = jnp.asarray(codes, jnp.int32)
    n = codes.shape[0]
    if n == 0:
        return np.zeros(0, out_dtype)
    pad_n = (-n) % TILE
    if pad_n:
        codes = jnp.pad(codes, (0, pad_n))
    if len(bits) <= ONEHOT_MAX:
        d_pad = padded_dict_size(max(len(bits), 1))
        bits = np.pad(bits, (0, d_pad - len(bits)))
    out = dict_decode(codes, jnp.asarray(bits),
                      interpret=interpret_mode())[:n]
    if out_dtype.kind == "f":
        out = jax.lax.bitcast_convert_type(out, jnp.float32)
    if out_dtype.itemsize == 8:                     # non-canonical in jax
        return np.asarray(out).astype(out_dtype)
    return out.astype(out_dtype)
