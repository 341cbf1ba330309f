"""``chip_smoke.py``: its phases at a tiny size on the CPU (interpreted
kernels), and its refusal to report a result without a TPU.

The script's real run needs the chip; these tests keep its code paths
from rotting between chip runs: the routing and byte-identity checks of
the scan phase, the train phase through ``repro.launch.train``, and the
four-device ingest comparison on virtual CPU devices."""

import importlib.util
import json
import shutil
import textwrap

import pytest

from subproc import REPO, run_python

# slow lane: jax/pallas compile-heavy; skipped by `make test-fast` / CI per-push
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _no_result(out):
    lines = out.stdout.strip().splitlines()
    return not lines or '"ok"' not in lines[-1]


def test_refuses_without_tpu():
    out = run_python([str(REPO / "chip_smoke.py")], timeout=300)
    assert out.returncode != 0
    assert _no_result(out)
    assert "needs 1 TPU" in out.stderr


def test_refuses_without_the_repository(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    out = run_python([str(tmp_path / "chip_smoke.py")], timeout=300)
    assert out.returncode != 0
    assert _no_result(out)


def test_scan_phase_routes_and_matches_numpy(chip_smoke):
    # 70k-row groups make the float32 columns DICT-encoded with
    # dictionaries far above ONEHOT_MAX (the XLA-gather route), next to
    # the small int32 dictionaries of the one-hot kernel route
    res = chip_smoke.scan_phase(seed=1, row_groups=2, rows_per_group=70_000)
    assert res["parquet"]["identical"] and res["adaptive"]["identical"]
    routing = res["parquet"]["routing"]
    for name in ("passenger_count", "trip_distance", "tip_amount"):
        assert routing["columns"][name] == {"kernel": 2}
    assert routing["predicate"] == {"kernel": 2}
    assert routing["compact"]["trip_id"] == {"kernel": 2}
    assert routing["columns"]["fare_amount"] == {"host": 2}  # float64
    assert res["rows_out"] > 0


def test_train_phase_through_the_launcher(chip_smoke):
    res = chip_smoke.train_phase(["--smoke", "--seq", "32", "--batch", "2",
                                  "--steps", "2"])
    assert len(res["losses"]) == 2
    assert res["config"].name == "gemma3-1b-smoke"


def test_multichip_phase_on_four_virtual_devices():
    code = textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.argv = ["chip_smoke.py"]
        import importlib.util, json
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(REPO / "chip_smoke.py")!r})
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        res = mod.multichip_phase(["--smoke", "--seq", "32", "--batch", "1",
                                   "--steps", "1"])
        print(json.dumps(res))
    """)
    out = run_python(["-c", code], timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "byte-identical to the concatenation of 4 ranks" in out.stdout
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert abs(res["loss_mesh"] - res["loss_one"]) <= 2e-3
