"""Batched serving demo: prompts out of the object store, waves of decode.

Stores prompt token streams columnar in the cluster, fetches them via
pushdown scans (projection = token column, predicate = prompt id), and
serves them through the wave-batching engine — the inference-side mirror
of the training ingest path.

    PYTHONPATH=src python examples/serve_batched.py
"""

import dataclasses
import time

import numpy as np

from repro.aformat.expressions import field
from repro.aformat.table import Table
from repro.configs import smoke_config
from repro.core import dataset, make_cluster, write_flat
from repro.launch.mesh import make_local_mesh
from repro.serve import Request, ServeEngine, init_serve_params
from repro.sharding import default_rules

VOCAB = 1024
N_PROMPTS = 12


def main():
    # -- prompts as a columnar table in the store ---------------------------
    fs = make_cluster(4)
    rng = np.random.default_rng(0)
    rows = {"prompt_id": [], "pos": [], "token": []}
    for pid in range(N_PROMPTS):
        n = int(rng.integers(4, 20))
        rows["prompt_id"] += [pid] * n
        rows["pos"] += list(range(n))
        rows["token"] += rng.integers(1, VOCAB, n).tolist()
    tbl = Table.from_pydict({
        "prompt_id": np.asarray(rows["prompt_id"], np.int64),
        "pos": np.asarray(rows["pos"], np.int32),
        "token": np.asarray(rows["token"], np.int32),
    })
    write_flat(fs, "/prompts/batch0.arw", tbl, row_group_rows=4096)
    ds = dataset(fs, "/prompts")

    # -- tiny model + engine ---------------------------------------------------
    cfg = smoke_config("starcoder2-7b")
    cfg = dataclasses.replace(cfg, num_layers=2, vocab_size=VOCAB,
                              remat=False)
    params, _ = init_serve_params(cfg, seed=0)
    engine = ServeEngine(cfg, make_local_mesh(1, 1), default_rules(),
                         params, max_batch=4)

    # -- fetch each prompt by pushdown scan, submit, run waves ----------------
    t0 = time.perf_counter()
    for pid in range(N_PROMPTS):
        out = ds.query(format="pushdown").filter(
            field("prompt_id") == pid).select("token").to_table()
        engine.submit(Request(pid, out.column("token").values.astype(
            np.int32), max_new_tokens=12))
    comps = engine.run()
    dt = time.perf_counter() - t0

    total = sum(len(c.tokens) for c in comps)
    print(f"served {len(comps)} requests in waves of "
          f"{engine.max_batch}: {total} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s on 1 CPU core)")
    for c in comps[:4]:
        print(f"  req {c.uid}: {len(c.tokens)} tokens, "
              f"prefill {c.prefill_s * 1e3:.0f} ms, "
              f"decode {c.decode_s * 1e3:.0f} ms")
    assert len(comps) == N_PROMPTS
    print("OK")


if __name__ == "__main__":
    main()
