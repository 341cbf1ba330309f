"""AOT compiles of the scan-path kernels for a described TPU v5e.

No chip is attached: the TPU compiler installed with jax compiles for a
described ``v5e:2x2`` topology and raises what the chip's compiler would
raise (block shapes off the (8, 128) tiling, layouts Mosaic refuses, too
much VMEM).  Interpret mode on the CPU shows none of that.  Every shape
is a full row group: 1.68M rows, parquet-mr's 128 MiB block of the
~80-byte taxi rows, padded to the kernels' tiles.

The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

import re

import pytest

import jax
import jax.numpy as jnp

from repro.kernels.dict_decode.dict_decode import (ONEHOT_MAX, dict_decode,
                                                   padded_dict_size)
from repro.kernels.dict_decode.dict_decode import TILE as DD_TILE
from repro.kernels.group_fold import group_fold as gf
from repro.kernels.predicate_fused.predicate_fused import (Program, Term,
                                                           predicate_mask)
from repro.kernels.predicate_fused.predicate_fused import TILE as PF_TILE
from repro.kernels.token_pack import ops as tp_ops

ROWS = (128 << 20) // 80                      # 1,677,721


def _padded(tile: int) -> int:
    return -(-ROWS // tile) * tile


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a TPU executable written to the persistent cache cannot be read
        # back without a chip; keep these compiles out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("d", [256, ONEHOT_MAX, 100_000])
def test_dict_decode_compiles(one_chip, d):
    n = _padded(DD_TILE)
    d_in = padded_dict_size(d) if d <= ONEHOT_MAX else d
    hlo = dict_decode.lower(_struct((n,), jnp.int32, one_chip),
                            _struct((d_in,), jnp.int32, one_chip),
                            interpret=False).compile().as_text()
    # one-hot route is the Pallas kernel; above ONEHOT_MAX an XLA gather
    assert ("tpu_custom_call" in hlo) == (d <= ONEHOT_MAX)


@pytest.mark.parametrize("c", [2, 4])
def test_predicate_mask_compiles(one_chip, c):
    n = _padded(PF_TILE)
    prog = Program(tuple(Term(i, op, 1.0) for i, op in
                         zip(range(c), ("ge", "gt", "lt", "ne"))), "and")
    hlo = predicate_mask.lower([_struct((n,), jnp.float32, one_chip)] * c,
                               prog, interpret=False).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_pack_tokens_compiles(one_chip):
    hlo = tp_ops._pack.lower(_struct((ROWS,), jnp.int32, one_chip),
                             _struct((ROWS,), jnp.bool_, one_chip),
                             capacity=1 << 21,
                             interpret=False).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_pack_tokens_merge_has_no_loop(one_chip):
    # the taxi cell's shape: one 900,790-row row group packed to 131,072
    # slots; the inter-tile merge finds each slot's tile without a search
    rows = 900_790
    hlo = tp_ops._pack.lower(_struct((rows,), jnp.int32, one_chip),
                             _struct((rows,), jnp.bool_, one_chip),
                             capacity=1 << 17,
                             interpret=False).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert not re.search(r"\swhile\(", hlo)


@pytest.mark.parametrize("sizes,terms", [
    ((3, 2), ((0,), (1,), (2,), (1, 2), (1, 3), (1, 2, 3))),
    ((), ((0, 1),)),
    ((16, 16), ((0,),)),
], ids=["q1", "q6_ungrouped", "most_groups"])
def test_group_fold_compiles(one_chip, sizes, terms):
    # a row group's kept rows, padded to the next power of two
    width = 1 << (ROWS - 1).bit_length()
    rows = len(sizes) + 1 + max(max(t) for t in terms)
    hlo = gf.group_fold.lower(_struct((rows, width), jnp.int32, one_chip),
                              _struct((), jnp.int32, one_chip),
                              nkeys=len(sizes), sizes=sizes, terms=terms,
                              interpret=False).compile().as_text()
    assert "tpu_custom_call" in hlo
