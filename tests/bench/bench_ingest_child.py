"""Child process of ``test_bench_ingest_correct.py``: the four-chip ingest
cell on four virtual CPU devices at a small size, once sound and once
under each fault; one JSON line per run on standard output."""

import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

import json  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from perfbench import harness, roofline, run  # noqa: E402


def run_small():
    """The four-chip ingest cell from its files, at a small size: it is
    ready but not yet in ``BENCHMARK.json``, for it has not run on four
    chips."""
    cell = harness.Cell(
        "lm_corpus.quality.client_pallas.x4", 4,
        harness.load_json(ROOT / "perfbench" / "configs" / "lm_corpus.json"),
        harness.load_json(harness.traffic_file("quality.client_pallas.x4",
                                               ROOT)),
        [{"name": "tokens_per_s", "unit": "tokens/s"},
         {"name": "setup_s", "unit": "s"}], [])
    # 20,000-row groups make the token column DICT-encoded, as at full
    # size (a 4,096-token sample of it then holds under len/4 distinct ids)
    cell.config.update(row_group_rows=20_000, seq_len=64, local_batch=2)
    return run.run_cell(cell, seed=2**31 + 9, seconds=0.5, trace=False,
                        started=time.perf_counter(), devices=jax.devices(),
                        peaks=roofline.PEAKS["TPU v5 lite"])


def token_altered():
    """Every token the dictionary-decode kernel produces, one higher."""
    import repro.kernels
    decode = repro.kernels.decode_dictionary

    def wrapped(codes, dictionary):
        out = np.array(decode(codes, dictionary))
        return out + 1 if out.dtype == np.int32 else out
    repro.kernels.decode_dictionary = wrapped


def exchange_left_out():
    """Every position of the mesh receives position 0's batch."""
    from repro.ingest import reader
    reader.MeshReader._rank = lambda self, index: 0


def half_left_out():
    """Each position's batch keeps half its rows, the rest repeated."""
    from repro.ingest import reader
    emit = reader.ShardedReader._emit

    def wrapped(self, st, need):
        batch, state = emit(self, st, need)
        half = len(batch["tokens"]) // 2
        for k in batch:
            batch[k][half:] = batch[k][:half]
        return batch, state
    reader.ShardedReader._emit = wrapped


FAULTS = {"token_altered": token_altered,
          "exchange_left_out": exchange_left_out,
          "half_left_out": half_left_out}


def main(which: str):
    if which != "sound":
        FAULTS[which]()
    result, checks, driver = run_small()
    out = {"run": which, "correct": result["correct"],
           "attempted": result["attempted"], "checks": result["checks"],
           "metrics": sorted(result["metrics"]),
           "count": result["device"]["count"]}
    if which == "sound":
        out["control_ok"] = all(c.ok for c in driver.check(control=True))
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    for which in sys.argv[1:]:
        main(which)
