"""Late materialization as the benchmark records it: on a Q6-like row
group the recording backend keeps the deferred decode at its kept,
bucketed rows and the codes' compaction as ``(rows, kept)``, the
rooflines read those calls, and ``deferred_decode_pct.scan`` reads the
share of decoded column chunks that the routing reports list as
deferred, and nothing where no report lists any (a program without
deferral)."""

import datetime
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import harness, roofline  # noqa: E402
from perfbench.drivers.tpch_agg import q6_filter  # noqa: E402
from perfbench.recorder import RecordingBackend  # noqa: E402
from repro.aformat import decode, parquet  # noqa: E402
from repro.aformat.schema import decimal64, schema  # noqa: E402
from repro.aformat.table import Table  # noqa: E402
from repro.kernels.dict_decode.dict_decode import ONEHOT_MAX  # noqa: E402

METRIC = "deferred_decode_pct.scan"
PARAMS = {"date": "1994-01-01", "discount": "0.06", "quantity": 24}
DAY0 = (datetime.date(1992, 1, 2) - datetime.date(1970, 1, 1)).days
V5E = roofline.PEAKS["TPU v5 lite"]


def read(reports):
    return harness.metric_reader(METRIC, ROOT)(
        SimpleNamespace(counters={"reports": reports}))


def report(columns, deferred):
    return {"columns": dict.fromkeys(columns, "kernel"),
            "predicate": "kernel", "compact": {}, "deferred": deferred}


TAXI = ["VendorID", "tpep_pickup_datetime", "passenger_count",
        "trip_distance", "PULocationID", "tip_amount", "fare_amount",
        "tpep_dropoff_datetime"]
Q6 = ["l_quantity", "l_extendedprice", "l_discount", "l_shipdate"]


@pytest.mark.parametrize("reports", [
    [report(TAXI, ["VendorID", "PULocationID"])] * 3,
    [report(Q6, ["l_extendedprice"])] * 5,
], ids=["taxi", "q6"])
def test_reads_the_share_of_deferred_chunks(reports):
    assert read(reports) == pytest.approx(25.0)


@pytest.mark.parametrize("reports", [
    [],
    [{"columns": {"a": "kernel"}, "predicate": "kernel",
      "compact": {"a": "kernel"}}],
], ids=["no-reports", "no-key"])
def test_nothing_to_read_gives_none(reports):
    assert read(reports) is None


@pytest.fixture(scope="module")
def q6_row_group():
    """One Q6-like row group: LINEITEM's four Q6 columns in their stored
    types, the price's dictionary above ONEHOT_MAX, the ship date's too."""
    rng = np.random.default_rng(18)
    n = 20_000
    dec = decimal64(15, 2)
    tbl = Table.from_pydict({
        "l_quantity": rng.integers(1, 51, n) * 100,
        "l_extendedprice": rng.integers(90_000, 10_495_000, 4_000)[
            rng.integers(0, 4_000, n)],
        "l_discount": rng.integers(0, 11, n),
        "l_shipdate": DAY0 + rng.integers(0, 2_526, n),
    }, schema(("l_quantity", dec), ("l_extendedprice", dec),
              ("l_discount", dec), ("l_shipdate", "date32")))
    for name in ("l_extendedprice", "l_shipdate"):
        assert len(np.unique(tbl.column(name).values)) > ONEHOT_MAX
    src = parquet.BytesSource(parquet.write_table(tbl, row_group_rows=n))
    return tbl, src, parquet.read_footer(src)


def test_the_recorder_keeps_the_deferred_calls(q6_row_group):
    tbl, src, meta = q6_row_group
    n = len(tbl)
    backend = RecordingBackend()
    backend.recording = True
    out = backend.scan_row_group(src, meta, meta.row_groups[0],
                                 ["l_extendedprice", "l_discount"],
                                 q6_filter(PARAMS))
    kept = len(out)
    assert 0 < kept < n
    capacity = 1 << (kept - 1).bit_length()
    rep, = backend.reports
    assert rep["deferred"] == ["l_extendedprice"]
    assert set(rep["columns"].values()) == {"kernel"}
    assert rep["compact"] == {"l_extendedprice": "kernel",
                              "l_discount": "kernel"}
    d = {name: len(np.unique(tbl.column(name).values)) for name in Q6}
    # the eager decodes in column order, then the deferred one at the
    # kept rows, bucketed as the compaction buckets them
    assert backend.decode_calls == [
        (n, d["l_quantity"]), (n, d["l_discount"]), (n, d["l_shipdate"]),
        (capacity, d["l_extendedprice"])]
    # the price's codes and the discount's values, compacted alike
    assert backend.pack_calls == [(n, kept)] * 2
    assert read(backend.reports) == pytest.approx(25.0)
    # the rooflines count the calls the scan made
    r = SimpleNamespace(
        counters={"decode_calls": backend.decode_calls,
                  "pack_calls": backend.pack_calls},
        module_s=lambda m: 0.01, peaks=V5E)
    decoded = sum(roofline.dict_decode_bytes(*c) for c in
                  backend.decode_calls)
    packed = 2 * roofline.pack_bytes(n, kept)
    assert harness.metric_reader("dict_decode_roofline.agg", ROOT)(r) == \
        pytest.approx(100 * decoded / V5E.hbm_bytes / 0.01)
    assert harness.metric_reader("token_pack_roofline.scan", ROOT)(r) == \
        pytest.approx(100 * packed / V5E.hbm_bytes / 0.01)
    # fewer bytes than the eager decode of the price at every row
    assert decoded < sum(roofline.dict_decode_bytes(n, d[c]) for c in Q6)


def test_the_deferred_result_is_the_host_paths(q6_row_group):
    _, src, meta = q6_row_group
    args = (src, meta, meta.row_groups[0], ["l_extendedprice",
                                            "l_discount"],
            q6_filter(PARAMS))
    want = decode.NumPyBackend().scan_row_group(*args)
    got = RecordingBackend().scan_row_group(*args)
    for a, b in zip(want.columns, got.columns):
        assert a.values.dtype == b.values.dtype
        assert a.values.tobytes() == b.values.tobytes()
