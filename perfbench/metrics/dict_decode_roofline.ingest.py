"""Roofline share of dictionary decode (``kernels/dict_decode``, module
``jit_dict_decode``) in an ingest cell, counted as in the scan cells."""

from perfbench import readers, roofline


def read(r):
    return readers.kernel_roofline_pct(r, "dict_decode", "decode_calls",
                                       roofline.dict_decode_bytes)
