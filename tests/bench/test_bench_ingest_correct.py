"""The four-chip ingest cell's correctness check, on four virtual CPU
devices at a small size: a sound run is correct and its control is not;
a token altered, the exchange between chips left out, or half a batch
left out makes the run not correct.  Faults are planted in separate
child processes, so that none leaks into another run."""

import json

import pytest

from subproc import REPO, run_python

pytestmark = pytest.mark.slow

CHILD = str(REPO / "tests" / "bench" / "bench_ingest_child.py")


def child(which):
    out = run_python([CHILD, which], timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct_and_its_control_is_not():
    got = child("sound")
    assert got["count"] == 4 and got["attempted"] > 0
    assert got["correct"]
    assert got["checks"] == {"batches_differing": {"value": 0, "limit": 0}}
    assert got["metrics"] == ["setup_s", "tokens_per_s"]
    assert not got["control_ok"]


@pytest.mark.parametrize("fault", ["token_altered", "exchange_left_out",
                                   "half_left_out"])
def test_broken_timed_path_is_not_correct(fault):
    got = child(fault)
    assert not got["correct"]
    assert got["checks"]["batches_differing"]["value"] > 0
