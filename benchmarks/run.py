"""Benchmark harness: one module per paper table/figure + the adaptation.

    PYTHONPATH=src python -m benchmarks.run           # everything
    PYTHONPATH=src python -m benchmarks.run fig5      # one benchmark

Each module prints its own CSV/claims and writes results/bench/<name>.json.
"""

from __future__ import annotations

import sys
import time

from benchmarks import (adaptive_scan, compaction, decode_backend,
                        encoding_advisor, fig5_latency_scaling,
                        fig6_cpu_utilization, ingest_train, kernel_bench,
                        layout_compare, multi_tenant, semi_join)
from repro.launch.compile_cache import use_compile_cache

BENCHES = {
    "fig5": fig5_latency_scaling.main,
    "fig6": fig6_cpu_utilization.main,
    "layout": layout_compare.main,
    "kernels": kernel_bench.main,
    "decode_backend": decode_backend.main,
    "ingest": ingest_train.main,
    "adaptive": adaptive_scan.main,
    "compaction": compaction.main,
    "semi_join": semi_join.main,
    "multi_tenant": multi_tenant.main,
    "encoding_advisor": encoding_advisor.main,
}


def main() -> int:
    names = sys.argv[1:] or list(BENCHES)
    use_compile_cache()
    failed = []
    for name in names:
        print(f"\n=== {name} " + "=" * (68 - len(name)), flush=True)
        t0 = time.perf_counter()
        try:
            BENCHES[name]()
        except Exception as e:  # noqa: BLE001 - report and continue
            failed.append(name)
            print(f"BENCH FAILED {name}: {type(e).__name__}: {e}")
        print(f"[{name}: {time.perf_counter() - t0:.1f}s]")
    if failed:
        print("\nFAILED:", ", ".join(failed))
        return 1
    print("\nall benchmarks complete")
    return 0


if __name__ == "__main__":
    sys.exit(main())
