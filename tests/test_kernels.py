"""Per-kernel shape/dtype sweeps: pallas (interpret) vs pure-jnp oracle."""

import numpy as np
import pytest

# slow lane: jax/pallas compile-heavy; skipped by `make test-fast` / CI per-push
pytestmark = pytest.mark.slow

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.kernels.dict_decode import ops as dd_ops
from repro.kernels.dict_decode.ref import dict_decode_ref
from repro.kernels.predicate_fused import ops as pf_ops
from repro.kernels.predicate_fused.predicate_fused import Program, Term
from repro.kernels.predicate_fused.ref import predicate_mask_ref
from repro.kernels.token_pack import ops as tp_ops
from repro.kernels.token_pack.ref import pack_ref, tile_pack_ref
from repro.kernels.token_pack.token_pack import TILE as TP_TILE, tile_pack


# ---------------------------------------------------------------------------
# predicate_fused
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [1, 100, 2048, 2049, 7777, 65536])
@pytest.mark.parametrize("dtype", [np.float32, np.int32, np.float64])
def test_predicate_shapes(n, dtype):
    rng = np.random.default_rng(n)
    cols = [rng.uniform(-100, 100, n).astype(dtype),
            rng.integers(0, 10, n).astype(np.int32)]
    prog = pf_ops.build_program([(0, "gt", 3.0), (1, "ne", 7)], "and")
    got = np.asarray(pf_ops.fused_predicate(cols, prog))
    exp = (cols[0].astype(np.float32) > 3.0) & (cols[1] != 7)
    assert np.array_equal(got, exp)


@pytest.mark.parametrize("op", ["lt", "le", "gt", "ge", "eq", "ne"])
@pytest.mark.parametrize("combine", ["and", "or"])
def test_predicate_ops(op, combine):
    rng = np.random.default_rng(3)
    cols = [rng.integers(-5, 5, 4096).astype(np.int32),
            rng.integers(-5, 5, 4096).astype(np.int32)]
    prog = pf_ops.build_program([(0, op, 0), (1, "ge", 2)], combine)
    stacked = jnp.stack([jnp.asarray(c, jnp.float32) for c in cols])
    got = np.asarray(pf_ops.fused_predicate(cols, prog))
    exp = np.asarray(predicate_mask_ref(stacked, prog)).astype(bool)
    assert np.array_equal(got, exp)


def test_predicate_negate():
    cols = [np.arange(2048, dtype=np.float32)]
    prog = Program((Term(0, "lt", 100.0),), "and", negate=True)
    got = np.asarray(pf_ops.fused_predicate(cols, prog))
    assert np.array_equal(got, np.arange(2048) >= 100)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 5000), st.floats(-50, 50), st.floats(-50, 50))
def test_predicate_property(n, t1, t2):
    rng = np.random.default_rng(n)
    cols = [rng.uniform(-60, 60, n).astype(np.float32),
            rng.uniform(-60, 60, n).astype(np.float32)]
    prog = pf_ops.build_program([(0, "ge", t1), (1, "lt", t2)], "or")
    got = np.asarray(pf_ops.fused_predicate(cols, prog))
    exp = (cols[0] >= np.float32(t1)) | (cols[1] < np.float32(t2))
    assert np.array_equal(got, exp)


# ---------------------------------------------------------------------------
# dict_decode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [1, 1024, 1025, 50_000])
@pytest.mark.parametrize("d", [1, 7, 128, 2048, 2049, 60_000])
def test_dict_decode_shapes(n, d):
    rng = np.random.default_rng(n + d)
    dic = rng.normal(size=d).astype(np.float32)
    codes = rng.integers(0, d, n).astype(np.int32)
    got = np.asarray(dd_ops.decode_dictionary(codes, dic))
    exp = np.asarray(dict_decode_ref(jnp.asarray(codes), jnp.asarray(dic)))
    np.testing.assert_allclose(got, exp, rtol=1e-6)


@pytest.mark.parametrize("dtype", [np.int32, np.int64, np.float64])
def test_dict_decode_dtypes(dtype):
    rng = np.random.default_rng(0)
    if np.issubdtype(dtype, np.integer):
        dic = rng.integers(0, 2 ** 20, 500).astype(dtype)
    else:
        dic = rng.normal(size=500).astype(dtype)
    codes = rng.integers(0, 500, 3000)
    got = np.asarray(dd_ops.decode_dictionary(codes, dic))
    assert got.dtype == dtype
    if np.issubdtype(dtype, np.integer):
        assert np.array_equal(got, dic[codes])
    else:
        np.testing.assert_allclose(got, dic[codes].astype(np.float32),
                                   rtol=1e-6)


def test_dict_decode_rejects_inexact_ints():
    dic = np.array([2 ** 25], np.int64)
    with pytest.raises(ValueError):
        dd_ops.decode_dictionary(np.zeros(10, np.int32), dic)


# ---------------------------------------------------------------------------
# token_pack
# ---------------------------------------------------------------------------


def test_tile_pack_kernel_stage():
    rng = np.random.default_rng(1)
    n = 4 * TP_TILE
    v = rng.normal(size=n).astype(np.float32)
    m = (rng.random(n) < 0.4).astype(np.int32)
    packed = tile_pack(jnp.asarray(v.view(np.int32)), jnp.asarray(m),
                       interpret=True)
    exp_p, _ = tile_pack_ref(v, m, TP_TILE)
    assert np.array_equal(np.asarray(packed).view(np.float32), exp_p)


@pytest.mark.parametrize("n", [1, 511, 512, 513, 10_000])
@pytest.mark.parametrize("density", [0.0, 0.05, 0.5, 1.0])
def test_pack_tokens_shapes(n, density):
    rng = np.random.default_rng(int(n + density * 10))
    vals = rng.integers(0, 2 ** 20, n).astype(np.int32)
    mask = rng.random(n) < density
    cap = max(64, n // 2)
    got, cnt = tp_ops.pack_tokens(vals, mask, cap)
    exp, exp_cnt = pack_ref(vals, mask, cap)
    assert int(cnt) == exp_cnt
    assert np.array_equal(np.asarray(got), exp)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 3000), st.floats(0, 1), st.integers(16, 2000))
def test_pack_tokens_property(n, density, cap):
    rng = np.random.default_rng(n)
    vals = rng.normal(size=n).astype(np.float32)
    mask = rng.random(n) < density
    got, cnt = tp_ops.pack_tokens(vals, mask, cap)
    exp, exp_cnt = pack_ref(vals, mask, cap)
    assert int(cnt) == exp_cnt
    np.testing.assert_allclose(np.asarray(got), exp, rtol=1e-6)


def _tile_mask(n, densities, seed):
    """Mask over n rows whose tile t keeps rows at densities[t]."""
    rng = np.random.default_rng(seed)
    tiles = -(-n // TP_TILE)
    dens = np.repeat(np.resize(np.asarray(densities, float), tiles), TP_TILE)
    return rng.random(n) < dens[:n]


def _rows_mask(n, rows):
    mask = np.zeros(n, bool)
    mask[list(rows)] = True
    return mask


# (n, mask, capacity): tiles with no kept rows share their successor's
# start; starts at or past capacity fall outside the output
_MERGE_EDGES = {
    "empty_leading": (4 * TP_TILE, _tile_mask(4 * TP_TILE, [0, 0, .5, .5], 1),
                      4 * TP_TILE),
    "empty_middle": (5 * TP_TILE, _tile_mask(5 * TP_TILE, [.5, 0, 0, 0, .3], 2),
                     5 * TP_TILE // 2),
    "empty_trailing": (4 * TP_TILE + 100,
                       _tile_mask(4 * TP_TILE + 100, [.5, .5, 0, 0, 0], 3),
                       4 * TP_TILE + 100),
    "all_kept_cap_below": (4 * TP_TILE, np.ones(4 * TP_TILE, bool),
                           TP_TILE + 3),
    "all_kept_cap_at_tile_start": (4 * TP_TILE, np.ones(4 * TP_TILE, bool),
                                   2 * TP_TILE),
    "half_kept_cap_below": (4 * TP_TILE, _tile_mask(4 * TP_TILE, [.5], 8),
                            3 * TP_TILE // 4),
    "capacity_1": (3 * TP_TILE, _tile_mask(3 * TP_TILE, [.3], 4), 1),
    "capacity_1_empty_leading": (3 * TP_TILE,
                                 _tile_mask(3 * TP_TILE, [0, 0, .3], 5), 1),
    "n_multiple_of_tile": (3 * TP_TILE, _tile_mask(3 * TP_TILE, [.5], 6),
                           3 * TP_TILE),
    "n_tile_minus_1": (TP_TILE - 1, _tile_mask(TP_TILE - 1, [.5], 7), TP_TILE),
    "n_tile_plus_1": (TP_TILE + 1, _rows_mask(TP_TILE + 1, [0, 7, TP_TILE]),
                      64),
    "one_row_last_slot_of_tile": (3 * TP_TILE,
                                  _rows_mask(3 * TP_TILE, [2 * TP_TILE - 1]),
                                  4),
}


@pytest.mark.parametrize("case", sorted(_MERGE_EDGES))
def test_pack_tokens_merge_edges(case):
    n, mask, cap = _MERGE_EDGES[case]
    vals = np.random.default_rng(n).integers(-2 ** 31, 2 ** 31, n,
                                             dtype=np.int64).astype(np.int32)
    got, cnt = tp_ops.pack_tokens(vals, mask, cap)
    exp, exp_cnt = pack_ref(vals, mask, cap)
    assert int(cnt) == exp_cnt
    got = np.asarray(got)
    assert got.dtype == exp.dtype and got.tobytes() == exp.tobytes()


def test_pack_preserves_order():
    vals = np.arange(2000, dtype=np.int32)
    mask = vals % 3 == 0
    got, cnt = tp_ops.pack_tokens(vals, mask, 1024)
    kept = np.asarray(got)[: int(cnt)]
    assert np.array_equal(kept, vals[mask][:1024])
    assert (np.diff(kept) > 0).all()
