"""Launcher entry points run end-to-end in smoke mode (subprocess: they
own XLA_FLAGS / argv)."""

import pytest

from subproc import run_python

# slow lane: jax/pallas compile-heavy; skipped by `make test-fast` / CI per-push
pytestmark = pytest.mark.slow


def _run(args):
    # generous timeout: CI containers can be CPU-throttled ~10x, and the
    # launcher subprocesses re-pay jax compilation from scratch
    return run_python(["-m", *args], timeout=1800)


def test_train_launcher_smoke():
    out = _run(["repro.launch.train", "--arch", "starcoder2-7b", "--smoke",
                "--steps", "12", "--batch", "2", "--seq", "32",
                "--ckpt-every", "6"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "done: ingest" in out.stdout
    assert "checkpoints=[6, 12]" in out.stdout


def test_serve_launcher_smoke():
    out = _run(["repro.launch.serve", "--arch", "starcoder2-7b", "--smoke",
                "--requests", "3", "--max-new", "4"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "served 3 requests" in out.stdout


def test_dryrun_single_cell():
    out = _run(["repro.launch.dryrun", "--arch", "whisper-small",
                "--shape", "decode_32k", "--mesh", "single",
                "--out", "/tmp/dryrun_test"])
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK   whisper-small__decode_32k__single" in out.stdout
