"""Decode engine: NumPy/Pallas backend equivalence + scheduler pricing.

The contract under test is byte-identity: for any row group the
``PallasBackend`` must return exactly what the ``NumPyBackend`` returns —
same dtypes, same bits, same validity — whether a column/predicate routed
through the accelerator kernels or fell back to the host path
(every kernel route is bit-exact; on the CPU the kernels run in Pallas's
interpreter, on the chip compiled, through the same code; the f32-domain
gates keep everything else on the host).  The grid here spans
encoding x dtype x validity x predicate, including fallback mixes inside
one row group.

Also pinned: the straight-lined DELTA decode at 0/1 rows, the vectorized
string materialization (ASCII / multi-byte UTF-8 / empty), the
scheduler's per-side decode-rate split (observations cross sides only
when the engines match; a Pallas prior moves the placement crossover),
and the ``decode_backend=``
plumbing through Dataset / resolve_format / explain().
"""

import numpy as np
import pytest

from repro.aformat import encodings, parquet
from repro.aformat.decode import (NumPyBackend, PallasBackend,
                                  resolve_backend)
from repro.aformat.expressions import IsIn, field
from repro.aformat.schema import schema
from repro.aformat.table import Column, Table, strings_from_buffers
from repro.core import dataset, make_cluster, write_flat
from repro.dataset.format import ParquetFormat, resolve_format
from repro.dataset.scheduler import ScanScheduler

NUMPY = NumPyBackend()
PALLAS = PallasBackend()


# ---------------------------------------------------------------------------
# differential harness
# ---------------------------------------------------------------------------


def assert_bytes_identical(a: Table, b: Table):
    """Stronger than Table.equals: exact bit patterns, even for floats."""
    assert a.schema.names == b.schema.names
    assert len(a) == len(b)
    for ca, cb in zip(a.columns, b.columns):
        assert ca.values.dtype == cb.values.dtype, ca.field.name
        if ca.field.type == "string":
            assert list(map(str, ca.values)) == list(map(str, cb.values))
        else:
            assert ca.values.tobytes() == cb.values.tobytes(), ca.field.name
        va = ca.validity if ca.validity is not None else \
            np.ones(len(ca), "?")
        vb = cb.validity if cb.validity is not None else \
            np.ones(len(cb), "?")
        assert np.array_equal(va, vb), ca.field.name


def scan_both(tbl, columns=None, predicate=None, row_group_rows=256):
    """Scan every row group with both backends; assert byte-identity and
    return (numpy result, pallas result, last pallas routing report)."""
    data = parquet.write_table(tbl, row_group_rows=row_group_rows)
    src = parquet.BytesSource(data)
    meta = parquet.read_footer(src)
    outs_np, outs_pl, report = [], [], {}
    for rg in meta.row_groups:
        out_np = NUMPY.scan_row_group(src, meta, rg, columns, predicate)
        report = {}
        out_pl = PALLAS.scan_row_group(src, meta, rg, columns, predicate,
                                       report=report)
        assert_bytes_identical(out_np, out_pl)
        outs_np.append(out_np)
        outs_pl.append(out_pl)
    return Table.concat(outs_np), Table.concat(outs_pl), report


def mixed_table(n=600, seed=0, with_nulls=False):
    """One row group's worth of every encoding/dtype regime: DELTA int64,
    DICT int32 (kernel-eligible), RLE int64, PLAIN float32/float64, DICT
    string, BITPACK bool, plus an out-of-f32-domain DICT int64."""
    rng = np.random.default_rng(seed)
    cols = {
        "seq": np.arange(n, dtype=np.int64),                    # DELTA
        "cat": rng.integers(0, 8, n).astype(np.int32),          # DICT
        "run": np.repeat(np.arange(n // 50 + 1, dtype=np.int64) * 1000,
                         50)[:n],                               # RLE
        "f32": rng.normal(0, 10, n).astype(np.float32),         # PLAIN
        "f64": rng.normal(0, 10, n).astype(np.float64),         # PLAIN
        "pay": rng.choice(["card", "cash", "disp"], n),         # DICT str
        "big": (rng.integers(0, 4, n).astype(np.int64)
                * 2 ** 30 + 7),             # DICT, outside f32 domain
        "flag": rng.integers(0, 2, n).astype(bool),             # BITPACK
    }
    tbl = Table.from_pydict(cols)
    if with_nulls:
        out = []
        for c in tbl.columns:
            if c.field.name in ("cat", "f32"):
                validity = rng.random(n) > 0.25
                out.append(Column(c.field, c.values, validity))
            else:
                out.append(c)
        tbl = Table(schema(*[(f.name, f.type) for f in tbl.schema],
                           nullable=("cat", "f32")), out)
    return tbl


PREDICATES = {
    "none": None,
    "flat-and": (field("cat") >= 2) & (field("f32") < 5.0),
    "flat-or": (field("cat") == 1) | (field("cat") == 6),
    "not": ~(field("cat") < 3),
    "three-way-and": ((field("cat") >= 1) & (field("f32") < 8.0)
                      & (field("seq") < 450)),
    "bool-eq": field("flag") == True,                           # noqa: E712
    "string-cmp": field("pay") == "cash",       # host: string column
    "f64-cmp": field("f64") > 0.0,              # host: float64 column
    "big-int": field("big") >= 2 ** 30,         # host: f32 domain
    "inexact-const": field("f32") < 0.1,        # host: 0.1 not f32-exact
    "isin": IsIn("cat", [1, 3, 5]),             # host: unsupported node
    "mixed-logic": ((field("cat") > 1) & (field("f32") < 5.0))
    | (field("seq") < 10),                      # host: AND under OR
    "empty-result": field("cat") > 99,          # selects nothing
}

PROJECTIONS = {
    "all": None,
    "numeric": ["seq", "cat", "f32"],
    "strings-only": ["pay"],
    "pred-col-dropped": ["seq", "f64"],
}


@pytest.mark.parametrize("pred_name", sorted(PREDICATES))
@pytest.mark.parametrize("nulls", [False, True], ids=["dense", "nulls"])
def test_backends_byte_identical(pred_name, nulls):
    tbl = mixed_table(with_nulls=nulls)
    out_np, out_pl, _ = scan_both(tbl, predicate=PREDICATES[pred_name])
    assert len(out_np) == len(out_pl)


@pytest.mark.parametrize("proj_name", sorted(PROJECTIONS))
def test_backends_byte_identical_projected(proj_name):
    tbl = mixed_table()
    scan_both(tbl, columns=PROJECTIONS[proj_name],
              predicate=PREDICATES["flat-and"])


def test_fallback_mix_within_one_row_group():
    """One row group where kernel and host columns coexist: the DICT int32
    rides the gather kernel, DELTA/strings/f64/big-int fall back, and the
    routing report says so explicitly."""
    tbl = mixed_table()
    _, _, report = scan_both(tbl, predicate=PREDICATES["flat-and"],
                             row_group_rows=len(tbl))
    assert report["columns"]["cat"] == "kernel"
    assert report["columns"]["seq"] == "host"      # DELTA byte stream
    assert report["columns"]["pay"] == "host"      # strings
    assert report["columns"]["big"] == "host"      # dict > f32 domain
    assert report["predicate"] == "kernel"
    assert report["compact"]["cat"] == "kernel"
    assert report["compact"]["pay"] == "host"


def test_large_dictionaries_and_dict_predicate_stay_on_kernels():
    """The chip's scan path at row-group scale: a DICT int32 column with a
    dictionary above ONEHOT_MAX (the gather route), a DICT float32 column
    likewise, next to a small one-hot DICT column; the predicate ANDs
    comparisons on the DICT columns and the selection compacts on the
    kernel.  Byte-identical to NumPy, and every route reports kernel."""
    from repro.kernels.dict_decode.dict_decode import ONEHOT_MAX

    rng = np.random.default_rng(7)
    n = 60_000
    tbl = Table.from_pydict({
        "id": rng.integers(0, 5_000, n).astype(np.int32),
        "amount": rng.choice(rng.gamma(1.5, 2.0, 3_000).astype(np.float32),
                             n),
        "count": rng.integers(1, 7, n).astype(np.int32),
    })
    data = parquet.write_table(tbl, row_group_rows=n)
    meta = parquet.read_footer(parquet.BytesSource(data))
    for name in ("id", "amount"):
        chunk = meta.row_groups[0].chunks[meta.schema.index(name)]
        assert chunk.encoding == encodings.DICT
        # the dictionary is the column's distinct values
        assert len(np.unique(tbl.column(name).values)) > ONEHOT_MAX
    q = float(np.float32(np.quantile(tbl.column("amount").values, 0.8)))
    pred = ((field("id") < 4_000) & (field("amount") > q)
            & (field("count") >= 2))
    _, out, report = scan_both(tbl, predicate=pred, row_group_rows=n)
    assert report["columns"] == {"id": "kernel", "amount": "kernel",
                                 "count": "kernel"}
    assert report["predicate"] == "kernel"
    assert report["compact"] == {"id": "kernel", "amount": "kernel",
                                 "count": "kernel"}
    assert 0 < len(out) < n


@pytest.mark.parametrize("pred_name,reason", [
    ("string-cmp", "pay:string"),
    ("f64-cmp", "f64:float64"),
    ("big-int", "big:f32-domain"),
    ("inexact-const", "f32:value"),
    ("isin", "unsupported-node"),
    ("mixed-logic", "unsupported-node"),
])
def test_predicate_fallback_reasons(pred_name, reason):
    tbl = mixed_table()
    _, _, report = scan_both(tbl, predicate=PREDICATES[pred_name],
                             row_group_rows=len(tbl))
    assert report["predicate"] == f"host:{reason}"


def test_validity_or_falls_back_and_stays_fused_under_and():
    """Nulls distribute over AND (validities post-ANDed into the kernel
    mask) but not over OR/NOT — those predicates must take the host path,
    and both routes must agree bit-for-bit."""
    tbl = mixed_table(with_nulls=True)
    _, _, rep_and = scan_both(tbl, predicate=PREDICATES["flat-and"],
                              row_group_rows=len(tbl))
    assert rep_and["predicate"] == "kernel"
    _, _, rep_or = scan_both(tbl, predicate=PREDICATES["flat-or"],
                             row_group_rows=len(tbl))
    assert rep_or["predicate"] == "host:cat:validity"
    _, _, rep_not = scan_both(tbl, predicate=PREDICATES["not"],
                              row_group_rows=len(tbl))
    assert rep_not["predicate"] == "host:cat:validity"


def test_scan_file_backend_equivalence(taxi_table):
    data = parquet.write_table(taxi_table, row_group_rows=2048)
    pred = (field("fare_amount") > 20.0) & (field("passenger_count") <= 2)
    a = parquet.scan_file(parquet.BytesSource(data), predicate=pred)
    b = parquet.scan_file(parquet.BytesSource(data), predicate=pred,
                          backend="pallas")
    assert_bytes_identical(a, b)


def test_resolve_backend():
    assert resolve_backend(None) is resolve_backend("numpy")
    assert resolve_backend("pallas") is resolve_backend("pallas")
    assert resolve_backend(PALLAS) is PALLAS
    with pytest.raises(ValueError, match="unknown decode backend"):
        resolve_backend("cuda")


# ---------------------------------------------------------------------------
# DELTA straight-line decode (regression: dead-expression cumsum)
# ---------------------------------------------------------------------------


def _delta_roundtrip(values):
    bufs = encodings.encode("int64", encodings.DELTA, values)
    return encodings.decode("int64", encodings.DELTA, bufs, len(values),
                            np.int64)


def test_delta_zero_rows():
    out = _delta_roundtrip(np.array([], np.int64))
    assert out.dtype == np.int64 and len(out) == 0


def test_delta_one_row():
    out = _delta_roundtrip(np.array([41], np.int64))
    assert out.tolist() == [41]


def test_delta_many_rows():
    vals = np.array([5, 6, 8, 8, 100, 101], np.int64)
    assert _delta_roundtrip(vals).tolist() == vals.tolist()


@pytest.mark.parametrize("n", [0, 1, 2])
def test_delta_tiny_row_groups_full_scan(n):
    """A 1-row trailing row group exercises the n==1 DELTA branch through
    the whole write/scan path (sorted ints pick DELTA)."""
    tbl = Table.from_pydict({"seq": np.arange(256 + n, dtype=np.int64)})
    data = parquet.write_table(tbl, row_group_rows=256)
    out = parquet.scan_file(parquet.BytesSource(data))
    assert out.equals(tbl)


# ---------------------------------------------------------------------------
# vectorized string materialization
# ---------------------------------------------------------------------------


def _string_bufs(strs):
    offs, payload = encodings._string_buffers(np.asarray(strs, object))
    return np.frombuffer(offs, np.int64), payload


@pytest.mark.parametrize("strs", [
    [],
    [""],
    ["", "", ""],
    ["abc", "", "defg"],
    ["héllo", "wörld", "naïve", ""],          # 2-byte UTF-8
    ["日本語", "a日b", "🙂🙂", "mixed🙂ascii"],  # 3- and 4-byte UTF-8
], ids=["empty", "one-empty", "all-empty", "ascii", "latin", "multibyte"])
def test_strings_from_buffers(strs):
    offsets, payload = _string_bufs(strs)
    out = strings_from_buffers(offsets, payload, len(strs))
    assert out.dtype == object
    assert out.tolist() == strs


def test_strings_from_buffers_prefix():
    """n smaller than the offsets array decodes just the prefix (the
    row-group tail case)."""
    offsets, payload = _string_bufs(["aa", "béé", "cc"])
    assert strings_from_buffers(offsets, payload, 2).tolist() == \
        ["aa", "béé"]


# ---------------------------------------------------------------------------
# scheduler: per-side decode-rate estimators
# ---------------------------------------------------------------------------


def test_observations_cross_sides_only_for_matching_engines(fs):
    # a numpy client runs the same engine as the OSD host path, so one
    # side's scan teaches both estimators (observations transfer — the
    # pre-split shared-EWMA behavior, which keeps a saturated cluster
    # flipping to the client even before any OSD scan has landed)
    sched = ScanScheduler(fs)
    sched._observe("client", 10_000_000, 0.1, 1000)
    assert sched._decode_rate_osd.value(0) == pytest.approx(1e8)
    assert sched._decode_rate_client.value(0) == pytest.approx(1e8)
    # a pallas client is a different engine: observations stay per side
    sched = ScanScheduler(fs, decode_backend="pallas")
    sched._observe("osd", 10_000_000, 0.1, 1000)
    assert sched._decode_rate_osd.value(0) == pytest.approx(1e8)
    assert sched._decode_rate_client._v is None   # untouched prior
    sched._observe("client", 10_000_000, 0.01, 1000)
    assert sched._decode_rate_client.value(0) == pytest.approx(1e9)
    assert sched._decode_rate_osd.value(0) == pytest.approx(1e8)


def test_client_prior_follows_backend(fs):
    assert ScanScheduler(fs)._client_rate_prior == \
        NumPyBackend.decode_rate_prior
    assert ScanScheduler(fs, decode_backend="pallas")._client_rate_prior \
        == PallasBackend.decode_rate_prior


def test_pallas_prior_moves_crossover(taxi_table):
    """Under moderate storage pressure a numpy client still prefers
    pushdown (its own decode is the bottleneck) while a Pallas client —
    priced by its ~10x decode prior — flips to client placement: the
    crossover the split estimators exist to move."""
    fs = make_cluster(8)
    write_flat(fs, "/d/part.arw", taxi_table.slice(0, 5000),
               row_group_rows=1024)
    frag = dataset(fs, "/d").fragments()[0]
    for osd in fs.store.osds:
        osd.background_load = 15 * osd.threads     # pressure ~16x
    est_np = ScanScheduler(fs, client_threads=1).estimate(frag)
    est_pl = ScanScheduler(fs, client_threads=1,
                           decode_backend="pallas").estimate(frag)
    assert est_np.where == "osd"
    assert est_pl.where == "client"
    assert est_pl.est_client_s < est_np.est_client_s
    assert est_pl.est_osd_s == pytest.approx(est_np.est_osd_s)


def test_adaptive_pallas_results_match_numpy(taxi_table):
    fs = make_cluster(8)
    for i in range(2):
        write_flat(fs, f"/d/part{i}.arw", taxi_table.slice(i * 5000, 5000),
                   row_group_rows=1024)
    ds = dataset(fs, "/d")
    pred = (field("fare_amount") > 25.0) & (field("passenger_count") >= 4)
    out_np = ds.query(format="adaptive").filter(pred).to_table()
    out_pl = ds.query(format="adaptive",
                      decode_backend="pallas").filter(pred).to_table()
    o = np.argsort(out_np.column("trip_id").values)
    p = np.argsort(out_pl.column("trip_id").values)
    assert_bytes_identical(out_np.take(o), out_pl.take(p))


# ---------------------------------------------------------------------------
# plumbing: decode_backend= through the Dataset API + explain()
# ---------------------------------------------------------------------------


@pytest.fixture
def flat_ds(taxi_table):
    fs = make_cluster(8)
    write_flat(fs, "/d/part.arw", taxi_table.slice(0, 5000),
               row_group_rows=1024)
    return dataset(fs, "/d"), taxi_table.slice(0, 5000)


def test_scanner_decode_backend(flat_ds):
    ds, tbl = flat_ds
    pred = field("passenger_count") >= 4
    out_np = ds.scanner(format="parquet", predicate=pred).to_table()
    out_pl = ds.scanner(format="parquet", predicate=pred,
                        decode_backend="pallas").to_table()
    assert_bytes_identical(out_np, out_pl)
    exp = int((tbl.column("passenger_count").values >= 4).sum())
    assert len(out_pl) == exp


def test_resolve_format_backend_errors():
    with pytest.raises(ValueError, match="pushdown"):
        resolve_format("pushdown", decode_backend="pallas")
    with pytest.raises(ValueError, match="constructor"):
        resolve_format(ParquetFormat(), decode_backend="pallas")


def test_explain_names_backend_and_routing(flat_ds):
    ds, _ = flat_ds
    pred = field("passenger_count") >= 4
    plan = ds.query(format="parquet", decode_backend="pallas") \
        .filter(pred).select("trip_id").explain()
    assert "backend=pallas[" in plan
    assert "pred=fused" in plan
    assert "passenger_count" in plan
    host_plan = ds.query(format="parquet").filter(pred).explain()
    assert "backend=numpy" in host_plan


def test_explain_adaptive_names_both_sides(flat_ds):
    ds, _ = flat_ds
    plan = ds.query(format="adaptive", decode_backend="pallas") \
        .filter(field("fare_amount") > 30.0).explain()
    assert "backend[client]=pallas[" in plan
    assert "backend[osd]=numpy" in plan


def test_describe_matches_live_routing(flat_ds):
    """The static (footer-only) routing explain() prints must agree with
    what the live scan actually does for DICT columns."""
    ds, _ = flat_ds
    frag = ds.fragments()[0]
    meta = frag.client_meta
    rg = meta.row_groups[frag.client_rg_index]
    desc = PALLAS.describe(meta, rg, ["passenger_count", "payment_type"],
                           None)
    assert "kernel=passenger_count" in desc
    assert "payment_type(dict)" in desc
