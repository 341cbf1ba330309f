"""Host spans of the scan path (``repro.trace``): the host-only modules
stay free of jax, and a client scan through the Pallas decode plane,
traced by the JAX profiler, shows every stage's span nested under its
task on the scanning thread, but for the inflates of the decode plane's
pool, which run on the pool's own threads."""

import sys
from pathlib import Path

import numpy as np
import pytest

from subproc import run_python

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402

from perfbench import spans  # noqa: E402
from repro.aformat.expressions import field  # noqa: E402
from repro.aformat.table import Table  # noqa: E402
from repro.core import dataset, make_cluster, write_flat  # noqa: E402
from repro.trace import span  # noqa: E402

#: every span the scan path writes, by layer
SPANS = {"repro.scan.task", "repro.storage.admit", "repro.storage.read",
         "repro.decode.decompress", "repro.decode.wait", "repro.decode.host",
         "repro.kernel.dict_decode", "repro.kernel.predicate",
         "repro.kernel.pack", "repro.kernel.fetch"}


def test_host_only_modules_do_not_import_jax():
    code = ("import sys, contextlib\n"
            "import repro.storage.cephfs, repro.aformat.decode, "
            "repro.dataset.format\n"
            "from repro.trace import span\n"
            "assert isinstance(span('repro.x'), contextlib.nullcontext)\n"
            "print('jax' in sys.modules)\n")
    out = run_python(["-c", code], timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == ["False"]


def test_span_is_the_profilers_annotation_once_jax_is_imported():
    assert isinstance(span("repro.x"), jax.profiler.TraceAnnotation)
    with span("repro.x"):
        pass


@pytest.fixture(scope="module")
def traced_scans(tmp_path_factory):
    """Two scans of one row group, a DICT int64 column and three float64
    ones, under the profiler: the float64 predicate stays on the host,
    the int64 one lowers to the predicate kernel.  Two of the float64
    columns are high-entropy, so their buffers are inflated on the
    decode plane's pool."""
    rng = np.random.default_rng(3)
    n = 12_000
    tbl = Table.from_pydict({
        "vendor": rng.integers(1, 7, n).astype(np.int64),
        "distance": np.round(rng.gamma(2.0, 1.5, n), 2),
        "fare": rng.random(n),
        "tip": rng.random(n),
    })
    fs = make_cluster(4)
    write_flat(fs, "/t/part.arw", tbl, row_group_rows=n)
    ds = dataset(fs, "/t")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tdir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        for pred in (field("distance") > 3.0, field("vendor") >= 3):
            q = ds.query(format="parquet", decode_backend="pallas") \
                .filter(pred).select("vendor", "distance", "fare", "tip")
            out = q.to_table()
            want = pred.evaluate(tbl)
            assert np.array_equal(out.column("vendor").values,
                                  tbl.column("vendor").values[want])
    finally:
        jax.profiler.stop_trace()
    _, host = spans.load(tdir)
    return [s for s in host if s.name.startswith("repro.")]


def on_task_threads(host):
    threads = {s.thread for s in host if s.name == spans.TASK}
    return [s for s in host if s.thread in threads]


def test_every_stage_span_nests_under_its_task(traced_scans):
    host = traced_scans
    assert {s.name for s in host} == SPANS
    tasks = [s for s in host if s.name == spans.TASK]
    assert len(tasks) == 2
    # the pool's inflates open and close on its own threads, which run
    # no task, and nothing else does
    on_tasks = on_task_threads(host)
    pool = [s for s in host if s not in on_tasks]
    assert {s.name for s in pool} == {"repro.decode.decompress"}
    assert len(pool) == 4           # two buffers a scan
    for s in on_tasks:
        if s.name in (spans.TASK, "repro.storage.admit"):
            continue
        assert any(t.thread == s.thread and t.start_ns <= s.start_ns
                   and s.end_ns <= t.end_ns for t in tasks), s
    # the slot is taken on the scanning thread just before the task
    for t in tasks:
        assert any(a.name == "repro.storage.admit" and a.thread == t.thread
                   and a.end_ns <= t.start_ns for a in host)
    # a blocking read of a kernel's result sits inside its kernel call
    calls = [s for s in host if s.name in ("repro.kernel.dict_decode",
                                           "repro.kernel.predicate",
                                           "repro.kernel.pack")]
    for f in (s for s in host if s.name == "repro.kernel.fetch"):
        assert any(c.thread == f.thread and c.start_ns <= f.start_ns
                   and f.end_ns <= c.end_ns for c in calls), f


def test_stage_self_times_add_up_to_the_task(traced_scans):
    host = on_task_threads(traced_scans)
    lo = min(s.start_ns for s in host)
    hi = max(s.end_ns for s in host)
    rows = 1_000_000
    got = spans.self_s_per_mrow(
        host, lo, hi, rows, spans.STAGES | {"task": (spans.TASK,),
                                            "wait": ("repro.decode.wait",)})
    assert all(v is not None and v >= 0 for v in got.values()), got
    task_s = sum(s.end_ns - s.start_ns for s in host
                 if s.name == spans.TASK) / 1e9
    assert sum(got.values()) == pytest.approx(task_s, rel=1e-9)


def test_a_traced_aggregate_folds_on_the_tasks_thread(tmp_path):
    """A client aggregate's fold of its partial state is the span
    ``repro.agg.fold``, after the scan's task span on the same thread."""
    from repro.aformat.schema import decimal64, schema

    rng = np.random.default_rng(5)
    n = 6_000
    tbl = Table.from_pydict(
        {"price": rng.integers(90_000, 10_000_000, n),
         "disc": rng.integers(0, 11, n)},
        schema(("price", decimal64(15, 2)), ("disc", decimal64(15, 2))))
    fs = make_cluster(4)
    for i in range(2):
        write_flat(fs, f"/agg/{i}/part.arw", tbl, row_group_rows=n)
    q = dataset(fs, "/agg").query(format="parquet", decode_backend="pallas") \
        .filter(field("disc") >= 5) \
        .aggregate([("sum", field("price") * field("disc"))])
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = q.to_table()
    finally:
        jax.profiler.stop_trace()
    keep = tbl.column("disc").values >= 5
    assert out.columns[0].values.tolist() == [2 * int(np.sum(
        tbl.column("price").values[keep] * tbl.column("disc").values[keep]))]
    _, host = spans.load(str(tmp_path))
    folds = [s for s in host if s.name == "repro.agg.fold"]
    tasks = [s for s in host if s.name == spans.TASK]
    assert len(folds) == len(tasks) == 2
    for f in folds:
        assert any(t.thread == f.thread and t.end_ns <= f.start_ns
                   for t in tasks), f
