"""The object-scan cells' correctness check, on the CPU at a small size:
a sound run is correct, the control is not, and a run with its timed path
broken underneath is not.

The harness's look for a chip is skipped: ``run_cell`` is driven on this
host's devices, the Pallas kernels interpreted."""

import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402

from perfbench import harness, roofline, run  # noqa: E402

pytestmark = pytest.mark.slow

CELL = "taxi_object.filter.client_pallas"
#: the cell's mix as is, and through the OSDs' ``scan_op`` (the driver's
#: other placement, which no cell runs yet)
CELLS = ["client_pallas", "pushdown"]


def small(name):
    cell = harness.resolve(harness.load_manifest(ROOT), CELL, ROOT)
    cell.config.update(rows_per_object=20_000, objects=3)
    if name == "pushdown":
        cell.traffic.update(format="pushdown", decode_backend=None)
    return cell


def run_small(name, seed=2**31 + 5):
    return run.run_cell(small(name), seed=seed, seconds=0.5, trace=False,
                        started=time.perf_counter(), devices=jax.devices(),
                        peaks=roofline.PEAKS["TPU v5 lite"])


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct_and_its_control_is_not(name):
    result, checks, driver = run_small(name)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 0
    assert [c.name for c in checks] == ["scans_differing", "scans_failed"]
    assert list(result["checks"]) == ["scans_differing", "scans_failed"]
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"scan_rows_per_s", "scan_p90_s",
                                      "setup_s"}
    # the tail and the rate are over every scan of the window
    lat = [s.done - s.issued for s in driver.scans]
    assert len(lat) == result["attempted"]
    assert result["metrics"]["scan_p90_s"]["value"] == pytest.approx(
        float(np.percentile(lat, 90)), rel=1e-12)
    # every scan done by the deadline counts, only the ones in flight then
    # (one per client at most) do not
    done = result["metrics"]["scan_rows_per_s"]["value"] * 0.5 / 20_000
    assert done == round(done)
    assert result["attempted"] - 4 <= done <= result["attempted"]
    control = driver.check(control=True)
    assert not all(c.ok for c in control)
    assert control[0].value == result["attempted"]


def _plus_one(fn):
    def wrapped(*args, **kwargs):
        out = np.array(fn(*args, **kwargs))
        return out + np.ones((), out.dtype)
    return wrapped


def _half(compact):
    def wrapped(self, tbl, mask, report=None):
        out = compact(self, tbl, mask, report)
        return out.slice(0, len(out) // 2)
    return wrapped


def answer_altered(monkeypatch, name):
    """Every decoded value one higher where it is produced: in the
    dictionary-decode kernel, or in the OSD's host decode."""
    if name == "client_pallas":
        import repro.kernels
        monkeypatch.setattr(repro.kernels, "decode_dictionary",
                            _plus_one(repro.kernels.decode_dictionary))
    else:
        from repro.aformat import encodings
        monkeypatch.setattr(encodings, "decode",
                            _plus_one(encodings.decode))


def half_left_out(monkeypatch, name):
    """The selection keeps half of its rows."""
    from repro.aformat import decode
    cls = decode.PallasBackend if name == "client_pallas" \
        else decode.NumPyBackend
    monkeypatch.setattr(cls, "compact", _half(cls.compact))


@pytest.mark.parametrize("fault", [answer_altered, half_left_out],
                         ids=["answer_altered", "half_left_out"])
@pytest.mark.parametrize("name", CELLS)
def test_broken_timed_path_is_not_correct(monkeypatch, name, fault):
    fault(monkeypatch, name)
    result, checks, _ = run_small(name)
    assert not result["correct"]
    assert result["checks"]["scans_differing"]["value"] > 0
