"""Roofline share of dictionary decode (``kernels/dict_decode``, module
``jit_dict_decode``) in an aggregate cell: the gather's bytes at the HBM
peak over the module's device time.  Both routes count alike."""

from perfbench import readers, roofline


def read(r):
    return readers.kernel_roofline_pct(r, "dict_decode", "decode_calls",
                                       roofline.dict_decode_bytes)
