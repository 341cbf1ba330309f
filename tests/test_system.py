"""End-to-end system test: the paper's storage stack feeding real training.

corpus -> object store (3-way replicated) -> pushdown-filtered sharded
reader -> train a tiny model -> checkpoint model+reader into the same
object store -> kill an OSD mid-run -> restore and continue.  This is
the full integration path of DESIGN.md §3 on one CPU device.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.aformat.expressions import field
from repro.configs import smoke_config
from repro.core import dataset, make_cluster
from repro.data import synth_corpus, write_corpus
from repro.distrib import CheckpointManager
from repro.ingest import ReaderConfig, ReaderState, ShardedReader
from repro.launch.mesh import make_local_mesh
from repro.sharding import default_rules
from repro.train import optim, step as step_mod


def test_end_to_end_train_with_pushdown_ingest():
    # --- storage: corpus into the simulated Ceph cluster -------------------
    fs = make_cluster(6)
    vocab = 256
    corpus = synth_corpus(150, mean_doc_len=300, vocab_size=vocab, seed=0)
    write_corpus(fs, "/corpus", corpus, num_shards=3, row_group_rows=8192)
    ds = dataset(fs, "/corpus")

    # --- ingest: storage-side quality filtering through the query plan -----
    rcfg = ReaderConfig(seq_len=32, local_batch=4,
                        predicate=field("quality") > 0.3,
                        format="pushdown", num_threads=2, seed=1)
    pipe = ShardedReader(ds, rcfg)

    # --- model + train step -------------------------------------------------
    cfg = smoke_config("starcoder2-7b")
    cfg = dataclasses.replace(cfg, num_layers=2, d_model=64, d_ff=128,
                              num_heads=2, num_kv_heads=2, head_dim=32,
                              vocab_size=vocab, remat=False)
    mesh = make_local_mesh(1, 1)
    rules = default_rules()
    opt = optim.OptConfig(peak_lr=1e-3, warmup_steps=5, decay_steps=100)
    state, spec_tree = step_mod.init_state(cfg, opt, jax.random.key(0))
    fn = jax.jit(step_mod.make_train_step(cfg, mesh, rules, opt))

    cm = CheckpointManager(fs, "/ckpt", keep=2)
    losses = []
    for step in range(8):
        batch = next(pipe)
        state, mets = fn(state, {k: jnp.asarray(v)
                                 for k, v in batch.items()})
        losses.append(float(mets["loss"]))
        if step == 4:
            # one commit point holds the model and the reader cut
            cm.save({"model": state,
                     "reader": pipe.checkpoint().to_arrays()}, step)

    assert all(np.isfinite(losses))
    # ingest really ran on the storage nodes
    st = pipe.stats()
    assert st["osd_cpu_s"] > 0 and st["client_cpu_s"] < st["osd_cpu_s"] * 5
    pipe.close()

    # --- failure + restore ----------------------------------------------------
    fs.store.fail_osd(0)
    structs = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                           state)
    restored = cm.restore({"model": structs,
                           "reader": ReaderState.restore_structs()}, 4)
    assert int(np.asarray(restored["model"]["step"])) == 5
    rstate = ReaderState.from_arrays(restored["reader"])
    # the restored reader continues the stream through the degraded store
    pipe2 = ShardedReader(ds, rcfg, state=rstate)
    batch = next(pipe2)
    state2, mets = fn(restored["model"], {k: jnp.asarray(v)
                                          for k, v in batch.items()})
    assert np.isfinite(float(mets["loss"]))
    pipe2.close()


def test_scan_consistency_under_failure_and_hedging():
    """Pushdown scans agree with client scans even with a down OSD and a
    straggling primary (hedged to a replica)."""
    fs = make_cluster(8)
    corpus = synth_corpus(60, mean_doc_len=150, vocab_size=100, seed=2)
    write_corpus(fs, "/c", corpus, num_shards=2, row_group_rows=2048)
    ds = dataset(fs, "/c")
    pred = field("domain") == 2

    ref = ds.query(format="parquet", num_threads=1).filter(pred) \
        .select("token").to_table()
    fs.store.fail_osd(1)
    fs.store.osds[2].straggle_factor = 50.0
    from repro.dataset import PushdownParquetFormat
    sc = ds.query(format=PushdownParquetFormat(hedge_threshold_s=1e-4),
                  num_threads=2).filter(pred).select("token")
    out = sc.to_table()
    assert np.array_equal(np.sort(out.column("token").values),
                          np.sort(ref.column("token").values))
