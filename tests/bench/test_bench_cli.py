"""``perfbench/run.py`` refuses to report without a TPU, and without the
rest of the repository beside it."""

import json
import os
import shutil
import subprocess
import sys

from subproc import REPO, run_python

ARGS = ["--workload", "taxi_object.filter.client_pallas", "--seed", "1",
        "--seconds", "1", "--trace", "0"]


def _no_result(out):
    lines = out.stdout.strip().splitlines()
    if not lines:
        return True
    try:
        return "correct" not in json.loads(lines[-1])
    except ValueError:
        return True


def test_refuses_without_a_tpu():
    out = run_python([str(REPO / "perfbench" / "run.py"), *ARGS],
                     timeout=300)
    assert out.returncode != 0 and _no_result(out)
    assert "needs 1 TPU chip(s)" in out.stderr


def test_refuses_with_only_the_benchmarks_files(tmp_path):
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in manifest["paths"]:
        shutil.copytree(REPO / p, tmp_path / p)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "perfbench/run.py", *ARGS],
                         capture_output=True, text=True, cwd=tmp_path,
                         env=env, timeout=300)
    assert out.returncode != 0 and _no_result(out)
