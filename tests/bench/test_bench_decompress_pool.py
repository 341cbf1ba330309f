"""``decompress_pool_pct.scan``: the share of compressed bytes that the
decode plane inflated on its pool, read from the routing reports the
recording backend keeps, and nothing where no report says (a program
without the pool)."""

import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perfbench import harness, readers  # noqa: E402
from perfbench.recorder import RecordingBackend  # noqa: E402
from repro.aformat import decode, parquet  # noqa: E402
from repro.aformat.expressions import field  # noqa: E402
from repro.aformat.table import Table  # noqa: E402
from repro.core import make_cluster, write_flat  # noqa: E402
from repro.storage.cephfs import FileSource  # noqa: E402

METRIC = "decompress_pool_pct.scan"


def read(reports):
    return harness.metric_reader(METRIC, ROOT)(
        SimpleNamespace(counters={"reports": reports}))


def test_reads_the_share_of_pooled_bytes():
    reports = [{"columns": {"a": "host"},
                "decompress": {"pool_bytes": 300, "inline_bytes": 100}},
               {"columns": {"a": "kernel"},
                "decompress": {"pool_bytes": 0, "inline_bytes": 600}}]
    assert read(reports) == pytest.approx(30.0)


@pytest.mark.parametrize("reports", [
    [],
    [{"columns": {"a": "kernel"}, "predicate": "host:a:float64",
      "compact": {"a": "kernel"}}],
    [{"columns": {"a": "host"},
      "decompress": {"pool_bytes": 0, "inline_bytes": 0}}],
], ids=["no-reports", "no-key", "no-bytes"])
def test_nothing_to_read_gives_none(reports):
    assert read(reports) is None


def test_reads_what_a_recorded_client_scan_reports():
    rng = np.random.default_rng(11)
    n = 24_000
    tbl = Table.from_pydict({
        "cat": rng.integers(0, 8, n).astype(np.int32),
        "x0": rng.random(n),
        "x1": rng.random(n),
    })
    fs = make_cluster(2, replication=1)
    write_flat(fs, "/t/part.arw", tbl, row_group_rows=n // 2)
    src = FileSource(fs, "/t/part.arw")
    meta = parquet.read_footer(src)
    backend = RecordingBackend()
    backend.recording = True
    for rg in meta.row_groups:
        backend.scan_row_group(src, meta, rg, ["cat", "x0", "x1"],
                               field("cat") >= 2)
    lengths = [ln for rg in meta.row_groups for c in rg.chunks
               for ln in c.buffer_lengths]
    pooled = sum(ln for ln in lengths if ln >= decode.POOL_MIN_BYTES)
    assert 0 < pooled < sum(lengths)
    assert read(backend.reports) == pytest.approx(
        100.0 * pooled / sum(lengths))
    # the routing share reads the same reports as before
    r = SimpleNamespace(counters={"reports": backend.reports})
    assert readers.kernel_route_pct(r) == pytest.approx(100.0 * 3 / 7)
