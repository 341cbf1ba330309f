"""Production serving launcher: prompts out of the object store -> waves.

Mirrors launch/train.py for the inference path: builds the cluster,
stores a batch of prompt token streams columnar, fetches each prompt via
a pushdown scan (projection + prompt-id predicate), and drives the
wave-batching engine.  --smoke runs the identical code path on one CPU
device with a reduced config:

    PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-7b \
        --smoke --requests 8 --max-new 8
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np

from repro.aformat.expressions import field
from repro.aformat.table import Table
from repro.configs import get_config, smoke_config
from repro.core import dataset, make_cluster, write_flat
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.serve import Request, ServeEngine, init_serve_params
from repro.sharding import default_rules


def store_prompts(fs, n: int, vocab: int, seed: int = 0) -> None:
    rng = np.random.default_rng(seed)
    pid, pos, tok = [], [], []
    for i in range(n):
        m = int(rng.integers(4, 24))
        pid += [i] * m
        pos += list(range(m))
        tok += rng.integers(1, vocab, m).tolist()
    tbl = Table.from_pydict({
        "prompt_id": np.asarray(pid, np.int64),
        "pos": np.asarray(pos, np.int32),
        "token": np.asarray(tok, np.int32),
    })
    write_flat(fs, "/prompts/wave0.arw", tbl, row_group_rows=8192)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="starcoder2-7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--osds", type=int, default=4)
    args = ap.parse_args()
    use_compile_cache()

    if args.smoke:
        cfg = smoke_config(args.arch)
        cfg = dataclasses.replace(cfg, num_layers=min(cfg.num_layers, 2),
                                  remat=False, vocab_size=1024)
        mesh = make_local_mesh(1, 1)
    else:
        cfg = get_config(args.arch)
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    rules = default_rules()

    fs = make_cluster(args.osds)
    store_prompts(fs, args.requests, cfg.vocab_size)
    ds = dataset(fs, "/prompts")

    params, _ = init_serve_params(cfg)
    engine = ServeEngine(cfg, mesh, rules, params,
                         max_batch=args.max_batch)
    t0 = time.perf_counter()
    wire = 0
    for i in range(args.requests):
        sc = ds.scanner(format="pushdown", columns=["token"],
                        predicate=field("prompt_id") == i)
        prompt = sc.to_table().column("token").values.astype(np.int32)
        wire += sc.metrics.wire_bytes
        engine.submit(Request(i, prompt, max_new_tokens=args.max_new))
    comps = engine.run()
    dt = time.perf_counter() - t0
    total = sum(len(c.tokens) for c in comps)
    print(f"arch={cfg.name} served {len(comps)} requests "
          f"({total} tokens) in {dt:.2f}s; prompt wire {wire / 1e3:.1f} KB "
          f"via pushdown")
    return 0 if len(comps) == args.requests else 1


if __name__ == "__main__":
    raise SystemExit(main())
