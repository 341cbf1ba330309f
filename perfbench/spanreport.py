"""Run one benchmark cell traced and break its window down by the
program's host spans.

    python3 perfbench/spanreport.py --workload <name> --seed <n> \
        --seconds <s> [--keep <dir>]

The run is ``run.py --trace 1``'s, through the same ``run_cell``, and its
result carries the same per-layer metrics and breakdown.  The trace is
read a second time by ``perfbench/spans.py``: the self time of each stage
of the scan path per million rows scanned, the longest idle gaps named by
the stage each host thread was in, and the share of each chip's idle time
during which no stage span was open.  ``--keep`` copies the profiler's
``.xplane.pb`` into that directory.  The last line of standard output is
the result with these under ``"spans"``.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from perfbench import harness, roofline, run, spans, tracereduce  # noqa: E402

#: beside the stages: the task's own time and the wait for an OSD slot
EXTRA = {"task_self_s_per_mrow": (spans.TASK,),
         "admit_s_per_mrow": ("repro.storage.admit",)}


def reduce(trace: tracereduce.Trace, host: list[spans.Span], rows: int,
           devices: list[int]) -> dict:
    lo, hi = trace.window()
    task_ns = sum(max(0.0, min(s.end_ns, hi) - max(s.start_ns, lo))
                  for s in host if s.name == spans.TASK)
    out = spans.self_s_per_mrow(host, lo, hi, rows, spans.STAGES | EXTRA)
    out["task_s_per_mrow"] = task_ns / 1e9 / (rows / 1e6) if rows else None
    out["idle_gaps"] = spans.idle_gaps(trace, host, devices)
    out["unattributed_idle_pct"] = {
        d: spans.unattributed_idle_pct(trace, host, d) for d in devices}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", help="directory to copy the .xplane.pb into")
    args = ap.parse_args(argv)
    cell = harness.resolve(harness.load_manifest(), args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"spanreport: {cell.name} needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return 2
    run.use_compile_cache(jax)
    # run_cell reads its trace through tracereduce.load and deletes it
    # after; read the host spans from the same directory on the way
    kept = {}
    load = tracereduce.load

    def load_and_keep(tdir):
        kept["trace"], kept["spans"] = spans.load(tdir)
        if args.keep:
            os.makedirs(args.keep, exist_ok=True)
            for f in glob.glob(os.path.join(tdir, "plugins", "profile",
                                            "*", "*.xplane.pb")):
                shutil.copy(f, args.keep)
        return kept["trace"]

    tracereduce.load = load_and_keep
    try:
        result, checks, driver = run.run_cell(
            cell, seed=args.seed, seconds=args.seconds, trace=True,
            started=run.STARTED, devices=devices,
            peaks=roofline.peaks(devices[0].device_kind))
    finally:
        tracereduce.load = load
    result["spans"] = reduce(kept["trace"], kept["spans"],
                             driver.counters().get("rows_scanned", 0),
                             [d.id for d in devices[:cell.chips]])
    print(f"spanreport: {cell.name} seed {args.seed} notes "
          f"{json.dumps(driver.notes)}", file=sys.stderr)
    print("\n".join(harness.check_lines(checks)), file=sys.stderr,
          flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
