"""Physical-design indexes: bloom blocks, FPR bounds, back-compat,
and the pruning-soundness differential.

The load-bearing invariants:

- a bloom verdict never drops a matching row (NONE is provable) — the
  differential scans with and without index blocks and compares bytes;
- footers written before index blocks existed load and scan unchanged
  (pinned against a serialized pre-change ARW1 file, generated with the
  unmodified writer before this subsystem landed);
- unknown index-block versions are skipped, not misread.
"""

import base64

import numpy as np
import pytest

from repro.aformat import parquet
from repro.aformat.expressions import (NONE, SOME, BloomIn, IsIn, field)
from repro.aformat.indexes import ColumnIndex, canonical_words, value_kind
from repro.aformat.table import Table
from repro.core import dataset, make_cluster, write_flat, write_split, \
    write_striped

WRITERS = {"flat": write_flat, "striped": write_striped,
           "split": write_split}


def _col(values, ftype, validity=None):
    from repro.aformat.schema import schema

    sch = schema(("x", ftype))
    return Table(sch, [parquet.Column(sch.field("x"),
                                      np.asarray(values), validity)]
                 ).column("x")


def _table(n=4000, seed=0):
    rng = np.random.default_rng(seed)
    return Table.from_pydict({
        "id": rng.permutation(np.arange(n, dtype=np.int64) * 13),
        "val": rng.normal(size=n).astype(np.float64),
        "tag": np.asarray([f"u{i:06d}" for i in range(n)], object),
    })


# ---------------------------------------------------------------------------
# ColumnIndex unit behavior
# ---------------------------------------------------------------------------


def test_build_counts_distinct_exactly():
    col = _col(np.asarray([5, 5, 7, 7, 7, 9], np.int64), "int64")
    idx = ColumnIndex.build(col)
    assert idx.kind == "i"
    assert idx.distinct == 3 and idx.count == 6


def test_build_skips_nulls():
    validity = np.asarray([True, False, True, False], "?")
    col = _col(np.asarray([1, 2, 3, 4], np.int64), "int64", validity)
    idx = ColumnIndex.build(col)
    assert idx.count == 2 and idx.distinct == 2
    assert idx.contains_any([1]) is True
    # 2 was masked out: the bloom may not claim it present
    assert idx.contains_any([2]) in (False, True)  # sound either way


def test_no_false_negatives_all_kinds():
    cases = [
        ("int64", np.arange(500, dtype=np.int64) * 7 - 1000),
        ("float64", np.linspace(-5.0, 5.0, 500)),
        ("string", np.asarray([f"key-{i}" for i in range(500)], object)),
    ]
    for ftype, vals in cases:
        idx = ColumnIndex.build(_col(vals, ftype))
        for v in vals[::37]:
            assert idx.contains_any([v]) is True, (ftype, v)


def test_fpr_bound():
    """At 8 bits/distinct with k=~5 hashes the theoretical FPR is ~2%;
    assert a generous 8% over a large absent-probe sample."""
    n = 4096
    idx = ColumnIndex.build(
        _col(np.arange(n, dtype=np.int64), "int64"))
    probes = np.arange(10_000, dtype=np.int64) * 3 + 1_000_000
    words = canonical_words("i", probes)
    hits = int(idx._probe_words(words).sum())
    assert hits / len(probes) < 0.08, hits


def test_probe_canonicalization_int_float():
    idx = ColumnIndex.build(_col(np.asarray([3, 8], np.int64), "int64"))
    # float probe 3.0 canonicalizes to int 3 -> present
    assert idx.contains_any([3.0]) is True
    # non-integral float can never equal an int value: no verdict abuse
    assert idx.contains_any([3.5]) is None
    assert canonical_words("i", ["not-an-int"]) is None


def test_value_kind_mapping():
    assert value_kind("int64") == value_kind("bool") == "i"
    assert value_kind("float32") == "f"
    assert value_kind("string") == "s"


# ---------------------------------------------------------------------------
# serialization: versioned block, unknown versions, pre-change footers
# ---------------------------------------------------------------------------


def test_index_json_roundtrip():
    idx = ColumnIndex.build(
        _col(np.arange(100, dtype=np.int64), "int64"))
    back = ColumnIndex.from_json(idx.to_json())
    assert back == idx


def test_unknown_version_skipped():
    idx = ColumnIndex.build(_col(np.arange(4, dtype=np.int64), "int64"))
    d = idx.to_json()
    d["v"] = 99
    assert ColumnIndex.from_json(d) is None
    assert ColumnIndex.from_json(None) is None
    assert ColumnIndex.from_json({}) is None


def test_footer_roundtrip_with_and_without_indexes():
    t = _table(600)
    data = parquet.write_table(t, row_group_rows=200)
    meta = parquet.read_footer(parquet.BytesSource(data))
    full = parquet.FileMeta.deserialize(meta.serialize())
    assert all(c.index is not None
               for rg in full.row_groups for c in rg.chunks)
    lean = parquet.FileMeta.deserialize(
        meta.serialize(include_indexes=False))
    assert all(c.index is None
               for rg in lean.row_groups for c in rg.chunks)
    # stripping indexes must not change any stats-visible field
    assert lean.num_rows == full.num_rows
    for a, b in zip(full.row_groups, lean.row_groups):
        assert [c.stats.min for c in a.chunks] == \
            [c.stats.min for c in b.chunks]


_GOLDEN_PRECHANGE_B64 = (
    "QVJXMXgBY2CAAAAACAABeAFjZEQBAACHABB4AWNgQAYP7CG8D1D6B5RmcICIs0BpDijNA6UF"
    "oLQQlBaB0mJQWgJKS0FpGSgt5wAAFNMHVngBY2BgYGAEYiYgZgZiECCFDwADIAAZeAFjYIAA"
    "RijNBKWZoTQLlAYAAMgAC3gBS0xKTgEAA9gBi3gBE2CAAAAAiAAReAFjZEQBAACHABB4AS3F"
    "xw0AIAwAsYxCDR1WyP5TIdD5Y5HH2U88B46cOLNy4cqNOw+evHjzsQuC/wZ5eAFjYGBgYARi"
    "JiBmBmIQIIUPAAMgABl4AWNggABGKM0EpZmhNAuUBgAAyAALeAFLTEpOAQAD2AGLeAFTYIAA"
    "AAEIACF4AWNkBAMAACMACHgBY2AAAQMHMNUApRkMoXwozWAE5UNpBmMo39gBAJ4IBY14AWNg"
    "gABGKM0EpZmhNAuUZoXSbFCaHUpzQGkABAgAJXgBS0xKTkkEYgAN2AMVeyJzY2hlbWEiOiB7"
    "ImZpZWxkcyI6IFt7Im5hbWUiOiAiaWQiLCAidHlwZSI6ICJpbnQ2NCIsICJudWxsYWJsZSI6"
    "IGZhbHNlfSwgeyJuYW1lIjogInZhbCIsICJ0eXBlIjogImZsb2F0NjQiLCAibnVsbGFibGUi"
    "OiBmYWxzZX0sIHsibmFtZSI6ICJ0YWciLCAidHlwZSI6ICJzdHJpbmciLCAibnVsbGFibGUi"
    "OiBmYWxzZX1dfSwgInJvd19ncm91cHMiOiBbeyJudW1fcm93cyI6IDE2LCAib2Zmc2V0Ijog"
    "NCwgInRvdGFsX2J5dGVzIjogMTMyLCAiY2h1bmtzIjogW3sib2Zmc2V0IjogNCwgImJ1ZmZl"
    "cl9sZW5ndGhzIjogWzExLCAxMV0sICJlbmNvZGluZyI6ICJkZWx0YSIsICJjb2RlYyI6ICJ6"
    "bGliIiwgInN0YXRzIjogeyJtaW4iOiAwLCAibWF4IjogMTUsICJudWxsX2NvdW50IjogMCwg"
    "ImNvdW50IjogMTZ9fSwgeyJvZmZzZXQiOiAyNiwgImJ1ZmZlcl9sZW5ndGhzIjogWzUzXSwg"
    "ImVuY29kaW5nIjogInBsYWluIiwgImNvZGVjIjogInpsaWIiLCAic3RhdHMiOiB7Im1pbiI6"
    "IDAuMCwgIm1heCI6IDcuNSwgIm51bGxfY291bnQiOiAwLCAiY291bnQiOiAxNn19LCB7Im9m"
    "ZnNldCI6IDc5LCAiYnVmZmVyX2xlbmd0aHMiOiBbMjMsIDIyLCAxMl0sICJlbmNvZGluZyI6"
    "ICJkaWN0IiwgImNvZGVjIjogInpsaWIiLCAic3RhdHMiOiB7Im1pbiI6ICJhIiwgIm1heCI6"
    "ICJkIiwgIm51bGxfY291bnQiOiAwLCAiY291bnQiOiAxNn19XX0sIHsibnVtX3Jvd3MiOiAx"
    "NiwgIm9mZnNldCI6IDEzNiwgInRvdGFsX2J5dGVzIjogMTI4LCAiY2h1bmtzIjogW3sib2Zm"
    "c2V0IjogMTM2LCAiYnVmZmVyX2xlbmd0aHMiOiBbMTEsIDExXSwgImVuY29kaW5nIjogImRl"
    "bHRhIiwgImNvZGVjIjogInpsaWIiLCAic3RhdHMiOiB7Im1pbiI6IDE2LCAibWF4IjogMzEs"
    "ICJudWxsX2NvdW50IjogMCwgImNvdW50IjogMTZ9fSwgeyJvZmZzZXQiOiAxNTgsICJidWZm"
    "ZXJfbGVuZ3RocyI6IFs0OV0sICJlbmNvZGluZyI6ICJwbGFpbiIsICJjb2RlYyI6ICJ6bGli"
    "IiwgInN0YXRzIjogeyJtaW4iOiA4LjAsICJtYXgiOiAxNS41LCAibnVsbF9jb3VudCI6IDAs"
    "ICJjb3VudCI6IDE2fX0sIHsib2Zmc2V0IjogMjA3LCAiYnVmZmVyX2xlbmd0aHMiOiBbMjMs"
    "IDIyLCAxMl0sICJlbmNvZGluZyI6ICJkaWN0IiwgImNvZGVjIjogInpsaWIiLCAic3RhdHMi"
    "OiB7Im1pbiI6ICJhIiwgIm1heCI6ICJkIiwgIm51bGxfY291bnQiOiAwLCAiY291bnQiOiAx"
    "Nn19XX0sIHsibnVtX3Jvd3MiOiA4LCAib2Zmc2V0IjogMjY0LCAidG90YWxfYnl0ZXMiOiAx"
    "MDIsICJjaHVua3MiOiBbeyJvZmZzZXQiOiAyNjQsICJidWZmZXJfbGVuZ3RocyI6IFsxMSwg"
    "MTFdLCAiZW5jb2RpbmciOiAiZGVsdGEiLCAiY29kZWMiOiAiemxpYiIsICJzdGF0cyI6IHsi"
    "bWluIjogMzIsICJtYXgiOiAzOSwgIm51bGxfY291bnQiOiAwLCAiY291bnQiOiA4fX0sIHsi"
    "b2Zmc2V0IjogMjg2LCAiYnVmZmVyX2xlbmd0aHMiOiBbMzRdLCAiZW5jb2RpbmciOiAicGxh"
    "aW4iLCAiY29kZWMiOiAiemxpYiIsICJzdGF0cyI6IHsibWluIjogMTYuMCwgIm1heCI6IDE5"
    "LjUsICJudWxsX2NvdW50IjogMCwgImNvdW50IjogOH19LCB7Im9mZnNldCI6IDMyMCwgImJ1"
    "ZmZlcl9sZW5ndGhzIjogWzMyLCAxNF0sICJlbmNvZGluZyI6ICJwbGFpbiIsICJjb2RlYyI6"
    "ICJ6bGliIiwgInN0YXRzIjogeyJtaW4iOiAiYSIsICJtYXgiOiAiZCIsICJudWxsX2NvdW50"
    "IjogMCwgImNvdW50IjogOH19XX1dLCAibnVtX3Jvd3MiOiA0MCwgImNyZWF0ZWRfYnkiOiAi"
    "cmVwcm8tYXJ3MSJ92AYAAEFSVzE="
)


def test_prechange_footer_loads_and_scans():
    """A file serialized by the writer BEFORE index blocks existed must
    load and scan byte-identically (backward compatibility)."""
    data = base64.b64decode(_GOLDEN_PRECHANGE_B64)
    src = parquet.BytesSource(data)
    meta = parquet.read_footer(src)
    assert meta.num_rows == 40 and len(meta.row_groups) == 3
    assert all(c.index is None
               for rg in meta.row_groups for c in rg.chunks)
    out = parquet.scan_file(src, predicate=(field("id") == 7))
    assert len(out) == 1
    assert out.column("val").values[0] == 3.5
    assert out.column("tag").values[0] == "d"
    # a no-index footer round-trips without growing an index field
    again = parquet.FileMeta.deserialize(meta.serialize())
    assert all(c.index is None
               for rg in again.row_groups for c in rg.chunks)


def test_write_table_build_indexes_off():
    t = _table(300)
    data = parquet.write_table(t, row_group_rows=100,
                               build_indexes=False)
    meta = parquet.read_footer(parquet.BytesSource(data))
    assert all(c.index is None
               for rg in meta.row_groups for c in rg.chunks)


# ---------------------------------------------------------------------------
# pruning: index verdicts at every choke point
# ---------------------------------------------------------------------------


def test_eq_isin_bloom_prune_upgrade():
    t = _table(2000)
    data = parquet.write_table(t, row_group_rows=250)
    meta = parquet.read_footer(parquet.BytesSource(data))
    sch = meta.schema
    ids = t.column("id").values
    stats = [rg.column_stats(sch) for rg in meta.row_groups]
    # a value inside every row group\'s [min, max] but present in exactly
    # one: stats say SOME everywhere, the bloom refutes the rest
    target = int(ids[len(ids) // 2])
    eq_verdicts = [(field("id") == target).prune(st) for st in stats]
    assert SOME in eq_verdicts
    assert eq_verdicts.count(NONE) >= len(stats) - 2
    isin = IsIn("id", [target])
    assert [isin.prune(st) for st in stats].count(NONE) >= len(stats) - 2
    bl = BloomIn.build("id", np.asarray([target], np.int64))
    bv = [bl.prune(st) for st in stats]
    assert SOME in bv and bv.count(NONE) >= len(stats) - 2
    # soundness: the row group that holds the value is never NONE
    hold = [i for i, rg in enumerate(meta.row_groups)
            if target in parquet.scan_row_group(
                parquet.BytesSource(data), meta, rg,
                ["id"]).column("id").values]
    for i in hold:
        assert eq_verdicts[i] != NONE
        assert bv[i] != NONE


def test_bloom_cross_kind_probe_is_skipped():
    t = _table(500)
    data = parquet.write_table(t, row_group_rows=500)
    meta = parquet.read_footer(parquet.BytesSource(data))
    st = meta.row_groups[0].column_stats(meta.schema)
    # float-keyed bloom probing the int64 "id" column: key domains
    # differ, so the index must NOT be consulted (stays SOME)
    bl = BloomIn.build("id", np.asarray([0.5, 1.5], np.float64))
    assert bl.key_kind == "f"
    assert bl.prune({"id": st["id"]}) == SOME


def test_bloom_wire_form_unchanged():
    bl = BloomIn.build("id", np.arange(10, dtype=np.int64))
    d = bl.to_json()
    assert "words" not in d and "key_kind" not in d
    from repro.aformat.expressions import Expr

    back = Expr.from_json(d)
    assert back.bits == bl.bits and back.words is None


# ---------------------------------------------------------------------------
# the soundness differential: with/without indexes, all formats
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("layout", ["flat", "striped", "split"])
@pytest.mark.parametrize("fmt", ["parquet", "pushdown", "adaptive"])
def test_index_pruning_soundness_differential(layout, fmt):
    """Whatever the index refutes must genuinely be absent: scanning
    with index blocks and scanning a physically identical no-index copy
    returns byte-identical rows, for every format x layout."""
    t = _table(3000, seed=3)
    ids = t.column("id").values
    fs_a, fs_b = make_cluster(4), make_cluster(4)
    WRITERS[layout](fs_a, "/d/t.arw", t, row_group_rows=250)
    if layout == "split":
        # split\'s per-rg files still index; the .index sidecar is stats-only
        WRITERS[layout](fs_b, "/d/t.arw", t, row_group_rows=250)
    else:
        data = parquet.write_table(t, row_group_rows=250,
                                   build_indexes=False)
        # write the same physical bytes minus index blocks
        if layout == "flat":
            su = max(4096, -(-len(data) // 4096) * 4096)
            fs_b.write_file("/d/t.arw", data, stripe_unit=su,
                            xattrs={"layout": "flat"})
        else:
            WRITERS[layout](fs_b, "/d/t.arw", t, row_group_rows=250)
    present = int(ids[17])
    absent = int(ids.max()) + 7   # inside no row group
    for target, expect_rows in ((present, 1), (absent, 0)):
        outs = []
        for fs in (fs_a, fs_b):
            ds = dataset(fs, "/d")
            out = ds.query(format=fmt, num_threads=2).filter(
                field("id") == target).to_table()
            outs.append(out)
        for out in outs:
            assert len(out) == expect_rows
        if expect_rows:
            for out in outs:
                assert out.column("id").values[0] == target
                row = int(np.flatnonzero(ids == target)[0])
                assert out.column("tag").values[0] == \
                    t.column("tag").values[row]


def test_point_lookup_wire_savings_client_format():
    """The acceptance bar: a bloom-indexed point lookup over a
    high-cardinality column ships <=10% of the stats-only wire bytes in
    the client-side format (chunk reads are the wire)."""
    t = _table(16_000, seed=9)
    ids = t.column("id").values
    fs_idx, fs_plain = make_cluster(4), make_cluster(4)
    write_flat(fs_idx, "/d/t.arw", t, row_group_rows=250)
    data = parquet.write_table(t, row_group_rows=250,
                               build_indexes=False)
    su = max(4096, -(-len(data) // 4096) * 4096)
    fs_plain.write_file("/d/t.arw", data, stripe_unit=su,
                        xattrs={"layout": "flat"})
    target = int(ids[31])
    wire = {}
    for name, fs in (("indexed", fs_idx), ("plain", fs_plain)):
        ds = dataset(fs, "/d")
        q = ds.query(format="parquet", num_threads=2).filter(
            field("id") == target)
        out = q.to_table()
        assert len(out) == 1 and out.column("id").values[0] == target
        wire[name] = q.metrics.wire_bytes - q.metrics.discovery_bytes
    assert wire["indexed"] <= 0.10 * wire["plain"], wire


def test_explain_names_index_verdicts(fs):
    t = _table(3000, seed=5)
    write_flat(fs, "/e/t.arw", t, row_group_rows=250)
    ds = dataset(fs, "/e")
    target = int(t.column("id").values[100])
    text = ds.query(format="pushdown").filter(
        field("id") == target).explain()
    assert "bloom index proves NONE" in text
    assert "by bloom index" in text


def test_scan_metrics_count_index_pruned(fs):
    t = _table(3000, seed=6)
    write_flat(fs, "/m/t.arw", t, row_group_rows=250)
    ds = dataset(fs, "/m")
    target = int(t.column("id").values[7])
    q = ds.query(format="pushdown").filter(field("id") == target)
    out = q.to_table()
    assert len(out) == 1
    s = q.metrics.summary()
    assert s["index_pruned"] >= s["pruned"] - 2 > 0
