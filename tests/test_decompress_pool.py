"""The decode plane's decompression pool (``decode.read_chunks``).

A client-side scan hands every buffer of at least ``POOL_MIN_BYTES`` to
one process-wide pool and decodes each column as soon as its own
buffers are inflated; an OSD's object-class call inflates every buffer
on its own thread.  Pinned here: the answer is byte-identical either
way, under both backends and under concurrent scans; an inflate's error
leaves ``scan_row_group`` as it did before and leaves no buffer
inflating; the routing report accounts for every compressed byte read;
and which sources and row groups engage the pool.
"""

import sys
import threading
import zlib

import numpy as np
import pytest

from repro.aformat import compression, decode, parquet
from repro.aformat.decode import NumPyBackend, PallasBackend
from repro.aformat.expressions import field
from repro.aformat.schema import schema
from repro.aformat.table import Column, Table
from repro.core import dataset, make_cluster, write_flat
from repro.storage.cephfs import FileSource

from test_decode import assert_bytes_identical

BACKENDS = {"numpy": NumPyBackend(), "pallas": PallasBackend()}
PRED = (field("cat") >= 2) & (field("f32") < 5.0)


class ClientBytes(parquet.BytesSource):
    """File bytes read as a client's task reads them."""

    client_side = True


def pool_table(n=12_000, seed=0, large=2):
    """One row group with ``large`` high-entropy float64 columns (a PLAIN
    buffer of about 8n bytes each, past the pool's threshold at this n)
    beside small buffers: a DICT int32 column (codes and dictionary), a
    nullable float32 (with its validity bitmap) and a DICT string."""
    rng = np.random.default_rng(seed)
    cols = {f"x{i}": rng.random(n) for i in range(large)}
    cols.update({
        "cat": rng.integers(0, 8, n).astype(np.int32),
        "f32": (np.round(rng.normal(0, 10, n)) + 0.0).astype(np.float32),
        "pay": rng.choice(["card", "cash", "disp"], n),
    })
    tbl = Table.from_pydict(cols)
    validity = rng.random(n) > 0.25
    out = [Column(c.field, c.values, validity)
           if c.field.name == "f32" else c for c in tbl.columns]
    return Table(schema(*[(f.name, f.type) for f in tbl.schema],
                        nullable=("f32",)), out)


def row_group(tbl):
    data = parquet.write_table(tbl, row_group_rows=len(tbl))
    meta = parquet.read_footer(parquet.BytesSource(data))
    return data, meta, meta.row_groups[0]


def large_buffers(meta, rg):
    return [ln for c in rg.chunks for ln in c.buffer_lengths
            if ln >= decode.POOL_MIN_BYTES]


@pytest.fixture(scope="module")
def mixed():
    tbl = pool_table()
    data, meta, rg = row_group(tbl)
    assert len(large_buffers(meta, rg)) == 2
    assert any(ln < decode.POOL_MIN_BYTES
               for c in rg.chunks for ln in c.buffer_lengths)
    return tbl, data, meta, rg


class RecordingPool:
    """The shared pool, keeping every future it hands out."""

    def __init__(self, pool):
        self.pool = pool
        self.futures = []

    def submit(self, *args):
        f = self.pool.submit(*args)
        self.futures.append(f)
        return f


@pytest.fixture
def recording_pool(monkeypatch):
    rec = RecordingPool(decode.decompress_pool())
    monkeypatch.setattr(decode, "decompress_pool", lambda: rec)
    return rec


@pytest.fixture
def inflating_threads(monkeypatch):
    """The name of the thread of every buffer inflate."""
    names = []
    real = compression.decompress

    def recording(codec, buf):
        names.append(threading.current_thread().name)
        return real(codec, buf)

    monkeypatch.setattr(compression, "decompress", recording)
    return names


@pytest.mark.parametrize("backend", sorted(BACKENDS))
@pytest.mark.parametrize("pred", [None, PRED], ids=["all", "filtered"])
def test_pooled_and_inline_scans_are_byte_identical(mixed, backend, pred):
    tbl, data, meta, rg = mixed
    be = BACKENDS[backend]
    rep_pool, rep_inline = {}, {}
    pooled = be.scan_row_group(ClientBytes(data), meta, rg, None, pred,
                               rep_pool)
    inline = be.scan_row_group(parquet.BytesSource(data), meta, rg, None,
                               pred, rep_inline)
    assert rep_pool["decompress"]["pool_bytes"] == sum(
        large_buffers(meta, rg))
    assert rep_inline["decompress"]["pool_bytes"] == 0
    assert_bytes_identical(pooled, inline)
    want = tbl if pred is None else tbl.filter(pred.evaluate(tbl))
    assert_bytes_identical(inline, want)
    assert rep_pool["columns"] == rep_inline["columns"]


def test_eight_threads_share_the_pool_and_get_the_reference(mixed):
    tbl, data, meta, rg = mixed
    want = tbl.filter(PRED.evaluate(tbl)).select(["x1", "cat", "pay"])
    got, errors = [], []

    def scan():
        try:
            for _ in range(3):
                got.append(NumPyBackend().scan_row_group(
                    ClientBytes(data), meta, rg, ["x1", "cat", "pay"],
                    PRED))
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)

    threads = [threading.Thread(target=scan) for _ in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors
    assert len(got) == 24
    for out in got:
        assert_bytes_identical(out, want)


def corrupt(data: bytes, meta, rg, name: str, index: int) -> bytes:
    """``data`` with the ZLIB header of buffer ``index`` of column
    ``name`` broken."""
    chunk = rg.chunks[meta.schema.index(name)]
    off = chunk.offset + sum(chunk.buffer_lengths[:index])
    return data[:off] + b"\x00\x00" + data[off + 2:]


@pytest.mark.parametrize("name,index", [("x0", 0), ("x3", 0), ("cat", 1)],
                         ids=["first-pooled", "last-pooled", "inline"])
def test_corrupt_buffer_raises_and_leaves_nothing_inflating(
        recording_pool, name, index):
    tbl = pool_table(large=4)
    data, meta, rg = row_group(tbl)
    bad = corrupt(data, meta, rg, name, index)
    with pytest.raises(zlib.error):
        NumPyBackend().scan_row_group(ClientBytes(bad), meta, rg)
    assert len(recording_pool.futures) == 4
    assert all(f.done() for f in recording_pool.futures)
    # the serial path raises the same error
    with pytest.raises(zlib.error):
        NumPyBackend().scan_row_group(parquet.BytesSource(bad), meta, rg)


def test_report_accounts_for_every_compressed_byte_read(mixed):
    _, data, meta, rg = mixed
    fs = make_cluster(2, replication=1)
    fs.write_file("/m.arw", data)
    read = []
    src = FileSource(fs, "/m.arw", on_read=read.append)
    rep = {}
    NumPyBackend().scan_row_group(src, meta, rg, ["x0", "x1", "cat"],
                                  field("f32") > 0.0, rep)
    sizes = rep["decompress"]
    assert sizes["pool_bytes"] > 0 and sizes["inline_bytes"] > 0
    assert sizes["pool_bytes"] + sizes["inline_bytes"] == sum(read)


@pytest.mark.parametrize("large,n,engaged", [
    (2, 12_000, True),     # two buffers past the threshold
    (1, 12_000, False),    # a single large buffer: nothing to overlap
    (2, 2_000, False),     # every buffer under the threshold
])
def test_client_file_source_engages_the_pool_above_the_threshold(
        inflating_threads, large, n, engaged):
    tbl = pool_table(n=n, large=large)
    fs = make_cluster(2, replication=1)
    write_flat(fs, "/t/part.arw", tbl, row_group_rows=n)
    src = FileSource(fs, "/t/part.arw")
    meta = parquet.read_footer(src)
    rep = {}
    out = NumPyBackend().scan_row_group(src, meta, meta.row_groups[0],
                                        None, None, rep)
    assert_bytes_identical(out, tbl)
    assert (rep["decompress"]["pool_bytes"] > 0) == engaged
    pooled = [t for t in inflating_threads if t.startswith("repro-inflate")]
    assert len(pooled) == (large if engaged else 0)
    # and through a client query
    inflating_threads.clear()
    got = dataset(fs, "/t").query(format="parquet").to_table()
    assert_bytes_identical(got, tbl)
    pooled = [t for t in inflating_threads if t.startswith("repro-inflate")]
    assert len(pooled) == (large if engaged else 0)


def test_osd_scan_op_inflates_every_buffer_on_its_own_thread(
        recording_pool, inflating_threads):
    tbl = pool_table()
    fs = make_cluster(4)
    write_flat(fs, "/t/part.arw", tbl, row_group_rows=len(tbl))
    got = dataset(fs, "/t").query(format="pushdown").filter(PRED) \
        .select("x0", "x1", "cat").to_table()
    assert_bytes_identical(
        got, tbl.filter(PRED.evaluate(tbl)).select(["x0", "x1", "cat"]))
    assert inflating_threads
    assert not any(t.startswith("repro-inflate")
                   for t in inflating_threads)
    assert recording_pool.futures == []
