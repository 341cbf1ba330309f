"""Training launcher.

Wires every layer together: mesh construction, per-arch config, the
object-store-backed data pipeline, the jitted train step, periodic
async checkpoints into the same object store, and failure recovery.

Full-scale use (on a real pod) takes --arch/--shape directly from the
registry.  --mesh local keeps the published widths on this host's own
devices (every device on the data axis, one reader per device, --seq and
--batch per device), with --layers cutting the depth.  --smoke shrinks
the model too, so the identical code path runs on one CPU device:

    PYTHONPATH=src python -m repro.launch.train --arch starcoder2-7b \
        --smoke --steps 50
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax

from repro.aformat.expressions import field
from repro.configs import SHAPES, get_config, smoke_config
from repro.core import dataset, make_cluster
from repro.data import synth_corpus, write_corpus
from repro.dataset.qos import TenantRegistry, ingest_context
from repro.distrib import CheckpointManager
from repro.ingest import MeshReader, ReaderConfig, ReaderState
from repro.launch import knobs as knobs_mod
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.sharding import default_rules
from repro.train import optim, step as step_mod


def build_training(cfg, mesh, rules, opt, *, num_microbatches=1):
    state, spec_tree = step_mod.init_state(cfg, opt, jax.random.key(0))
    from repro.sharding import tree_shardings

    state_specs = {
        "params": spec_tree,
        "opt": {"m": optim.moment_specs(spec_tree, state["opt"]["m"]),
                "v": optim.moment_specs(spec_tree, state["opt"]["v"]),
                "count": None},
        "step": None,
    }
    shardings = tree_shardings(mesh, rules, state, state_specs)
    state = jax.device_put(state, shardings)
    fn = jax.jit(step_mod.make_train_step(cfg, mesh, rules, opt,
                                          num_microbatches=num_microbatches),
                 donate_argnums=(0,))
    return state, state_specs, fn


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="starcoder2-7b")
    ap.add_argument("--shape", default="train_4k", choices=list(SHAPES))
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config + local mesh (CPU-runnable)")
    ap.add_argument("--mesh", default="production",
                    choices=["production", "local"],
                    help="production: the fixed pod mesh and --shape; "
                         "local: this host's devices on the data axis")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to this many layers (widths stay)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--knobs", default="baseline")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8,
                    help="sequences per data-axis position (local mesh)")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--quality", type=float, default=0.5,
                    help="pushdown quality-filter threshold")
    ap.add_argument("--osds", type=int, default=8)
    ap.add_argument("--format", default="pushdown",
                    choices=["pushdown", "parquet", "adaptive"])
    ap.add_argument("--decode-backend", default=None,
                    choices=["numpy", "pallas"],
                    help="client decode engine (parquet/adaptive formats)")
    ap.add_argument("--resume", action="store_true",
                    help="restore model + reader from the latest "
                         "checkpoint and continue the exact batch stream")
    return ap.parse_args(argv)


def build_model(args: argparse.Namespace):
    """(cfg, mesh, seq_len, per-position batch, optimizer) of a run."""
    if args.smoke:
        cfg = smoke_config(args.arch)
        cfg = dataclasses.replace(cfg, remat=False,
                                  vocab_size=4096,
                                  num_layers=min(cfg.num_layers, 2))
    else:
        cfg = get_config(args.arch)
        cfg = knobs_mod.get(args.knobs, args.arch, args.shape).apply(cfg)
    if args.layers:
        cfg = dataclasses.replace(cfg, num_layers=args.layers)
    if args.smoke or args.mesh == "local":
        mesh = make_local_mesh(jax.device_count(), 1)
        seq, batch = args.seq, args.batch
    else:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        shape = SHAPES[args.shape]
        seq = shape.seq_len
        batch = shape.global_batch // int(mesh.shape["data"])
    opt = optim.OptConfig(peak_lr=1e-3, warmup_steps=10,
                          decay_steps=max(100, args.steps),
                          moment_dtype=cfg.opt_moment_dtype)
    return cfg, mesh, seq, batch, opt


def build_ingest(args: argparse.Namespace, cfg, seq: int, batch: int):
    """(object store, dataset, reader config): a synthetic corpus from
    seed 0, sharded over the OSDs, read as a bulk-lane tenant."""
    fs = make_cluster(args.osds)
    corpus = synth_corpus(1200, mean_doc_len=400,
                          vocab_size=cfg.vocab_size, seed=0)
    write_corpus(fs, "/corpus", corpus, num_shards=args.osds,
                 row_group_rows=16384)
    ds = dataset(fs, "/corpus")
    # the training reader is a registered bulk-lane tenant: interactive
    # queries against the same cluster are arbitrated against it by the
    # shared weighted-fair admission controller, not starved by it
    registry = TenantRegistry()
    rcfg = ReaderConfig(seq_len=seq, local_batch=batch,
                        predicate=field("quality") > args.quality,
                        format=args.format, num_threads=2,
                        decode_backend=args.decode_backend,
                        tenant=ingest_context(registry), registry=registry)
    return fs, ds, rcfg


def run(args: argparse.Namespace) -> dict:
    """Train ``args.steps`` steps; returns the per-step losses, the
    ingest totals and the device's peak memory (None where the backend
    does not report it)."""
    cfg, mesh, seq, batch, opt = build_model(args)
    rules = default_rules()
    fs, ds, rcfg = build_ingest(args, cfg, seq, batch)
    cm = CheckpointManager(fs, "/ckpt", keep=3)

    # -- train state (+ optional resume) -------------------------------------
    state, state_specs, fn = build_training(cfg, mesh, rules, opt)
    start_step = 0
    reader = MeshReader(ds, rcfg, mesh)
    if args.resume:
        last = cm.latest_step()
        if last is None:
            print("--resume: no checkpoint found, starting fresh")
        else:
            from repro.sharding import tree_shardings

            shardings = tree_shardings(mesh, rules, state, state_specs)
            state = cm.restore({"model": state}, last,
                               shardings={"model": shardings})["model"]
            rstates = [ReaderState.from_arrays(a) for a in cm.restore(
                {"reader": [ReaderState.restore_structs()
                            for _ in reader.readers]}, last)["reader"]]
            reader.close()
            reader = MeshReader(ds, rcfg, mesh, states=rstates)
            start_step = last
            print(f"--resume: step {last}, reader at epoch "
                  f"{rstates[0].epoch} cursor {rstates[0].cursor}")

    # -- train loop ----------------------------------------------------------------
    first = next(iter(reader.readers.values()))
    print(f"arch={cfg.name} layers={cfg.num_layers} "
          f"params~{cfg.param_count() / 1e6:.1f}M "
          f"mesh={dict(mesh.shape)} ingest={args.format} "
          f"decode={first.fmt.__class__.__name__}"
          f"/{args.decode_backend or 'numpy'} "
          f"ranks {sorted(reader.readers)} of {reader.dp_size} "
          f"({len(first.tasks)} tasks) batch={reader.shape}")
    losses = []
    t0 = time.perf_counter()
    for step in range(start_step + 1, start_step + args.steps + 1):
        state, mets = fn(state, next(reader))
        losses.append(mets["loss"])
        if step % 10 == 0 or step == start_step + 1:
            loss = float(mets["loss"])
            toks = (step - start_step) * reader.shape[0] * seq
            dt = time.perf_counter() - t0
            print(f"step {step:5d} loss {loss:7.4f} "
                  f"tok/s {toks / dt:9.0f} lr {float(mets['lr']):.2e}",
                  flush=True)
        if step % args.ckpt_every == 0:
            # reader states ride in the same checkpoint as the model:
            # one commit point restores both to the same cut
            cm.save_async({"model": state,
                           "reader": [st.to_arrays()
                                      for st in reader.checkpoint()]},
                          step)
    cm.wait()
    reader.close()
    ing = reader.stats()
    print(f"done: ingest host_cpu={ing['client_cpu_s']}s "
          f"storage_cpu={ing['osd_cpu_s']}s "
          f"wire={ing['wire_bytes'] / 1e6:.1f}MB "
          f"batches={ing['batches']} checkpoints={cm.steps()}")
    mem = jax.devices()[0].memory_stats() or {}
    return {"losses": [float(x) for x in losses], "ingest": ing,
            "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
            "config": cfg, "mesh": dict(mesh.shape), "seq": seq,
            "batch": reader.shape[0]}


def main(argv=None) -> int:
    args = parse_args(argv)
    use_compile_cache()
    run(args)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
