"""The control of a cell's correctness check, read beside the program's.

    python3 perfbench/control.py --workload <name> --seeds 1,2,3 \
        --seconds <s>

For each seed, one run of the cell as ``run.py`` makes it, whose answers
are then compared twice: with the plain reference (the program's reading)
and with the control, the reference computed in the next precision below
the one the configuration states (the control's reading).  The control
has to come out not correct.  Prints one JSON line per seed; needs the
chips the cell asks for.
"""

from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from perfbench import harness, roofline, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.resolve(harness.load_manifest(), args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"control: {cell.name} needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return 2
    run.use_compile_cache(jax)
    for seed in [int(s) for s in args.seeds.split(",")]:
        result, checks, driver = run.run_cell(
            cell, seed=seed, seconds=args.seconds, trace=False,
            started=time.perf_counter(), devices=devices,
            peaks=roofline.peaks(devices[0].device_kind))
        control = driver.check(control=True)
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "attempted": result["attempted"],
            "program": {c.name: c.value for c in checks},
            "control": {c.name: c.value for c in control},
            "control_correct": all(c.ok for c in control)}), flush=True)
        del driver
    return 0


if __name__ == "__main__":
    sys.exit(main())
