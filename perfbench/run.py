"""Run one benchmark cell on the chips of this machine.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up makes the cell's data from ``--seed``, builds the object store,
and scans once everything the window will scan, so that every program is
compiled (or found in JAX's persistent cache, ``$JAX_COMPILATION_CACHE_DIR``
or else ``.jax_cache/`` in the checkout).  The window then runs the
cell's traffic for ``--seconds``.  Afterwards every answer of the window
is compared with the plain reference.

With ``--trace 0`` the result's metrics are the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the profiler, and they
are its per-layer metrics, with the device's busy time and a breakdown.
The last line of standard output is the result as one JSON object; the
last lines of standard error are the numbers compared, each with its
limit.  Without a TPU, or with fewer chips than the cell asks for, the
run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from perfbench import harness, roofline, tracereduce  # noqa: E402


@dataclasses.dataclass
class Readings:
    """What a per-layer metric reads: the driver's counters, the trace
    and its reduction, and the chip's peaks."""

    counters: dict
    trace: tracereduce.Trace
    summary: tracereduce.Summary
    peaks: roofline.Peaks

    def module_s(self, module: str) -> float:
        lo, hi = self.trace.window()
        return tracereduce.module_s(self.trace, module, lo, hi)


def use_compile_cache(jax):
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # the kernels and the small programs around them compile in well
    # under JAX's default threshold of one second: cache them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def peak_bytes(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def run_cell(cell: harness.Cell, *, seed: int, seconds: float,
             trace: bool, started: float, devices, peaks: roofline.Peaks
             ) -> tuple[dict, list, object]:
    """One run of ``cell`` on ``devices``, whose peaks are ``peaks``:
    (result, checks, the driver, whose answers can be checked again)."""
    import jax
    from jax.profiler import TraceAnnotation

    driver = harness.driver_module(cell.config["driver"]).Driver(cell, seed)
    driver.setup()
    setup_s = time.perf_counter() - started
    with tempfile.TemporaryDirectory(prefix="perfbench-trace-") as tdir:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
            driver.recording(True)
        try:
            with TraceAnnotation(tracereduce.WINDOW_SPAN):
                e2e = driver.window(seconds)
        finally:
            if trace:
                driver.recording(False)
                jax.profiler.stop_trace()
        used = devices[:cell.chips]
        device = {"platform": used[0].platform,
                  "kind": used[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": peak_bytes(used)}
        counters = driver.counters()
        readings = None
        if trace:
            tr = tracereduce.load(tdir)
            summary = tracereduce.summarize(tr, [d.id for d in used])
            readings = Readings(counters, tr, summary, peaks)
    driver.release()
    checks = driver.check()
    metrics = {}
    if readings is None:
        for m in cell.end_to_end:
            value = setup_s if m["name"] == "setup_s" else e2e[m["name"]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            value = harness.metric_reader(m["name"])(readings)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=readings.summary.mean_busy_s,
                      window_s=readings.summary.window_s)
    result = {"correct": all(c.ok for c in checks),
              "attempted": driver.attempted, "failed": driver.failed,
              "metrics": metrics, "device": device}
    if readings is not None:
        result["breakdown"] = {"device_ops": readings.summary.device_ops,
                               "idle_gaps": readings.summary.idle_gaps}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result, checks, driver


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    cell = harness.resolve(harness.load_manifest(), args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"perfbench: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    use_compile_cache(jax)
    result, checks, driver = run_cell(cell, seed=args.seed, seconds=args.seconds,
                              trace=bool(args.trace), started=STARTED,
                              devices=devices,
                              peaks=roofline.peaks(devices[0].device_kind))
    sys.stdout.flush()
    print(f"perfbench: {cell.name} seed {args.seed} notes "
          f"{json.dumps(driver.notes)}", file=sys.stderr)
    print("\n".join(harness.check_lines(checks)), file=sys.stderr,
          flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
