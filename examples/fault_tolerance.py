"""Fault-tolerance tour: failover, hedging, checkpoint restore, elastic.

Walks the four recovery mechanisms end to end on the simulated cluster:
  1. OSD failure mid-workload -> replicas serve reads and scan_ops;
  2. a straggling OSD -> hedged scan beats the tail;
  3. training state restored from object-store checkpoints after a crash;
  4. elastic downsize: lose half the fleet, re-mesh, keep training
     (on 8 virtual CPU devices, in this same process).

    PYTHONPATH=src python examples/fault_tolerance.py
"""

import os

# 8 virtual CPU devices for the elastic demo; must precede the jax import
os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS"), "--xla_force_host_platform_device_count=8")))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import dataset, make_cluster  # noqa: E402
from repro.data import synth_corpus, write_corpus  # noqa: E402
from repro.dataset import PushdownParquetFormat  # noqa: E402
from repro.distrib import CheckpointManager, HealthMonitor  # noqa: E402


def demo_failover():
    print("=== 1. OSD failure: replicas serve the scan ===")
    fs = make_cluster(8)
    corpus = synth_corpus(200, mean_doc_len=200, vocab_size=500, seed=1)
    write_corpus(fs, "/c", corpus, num_shards=4)
    ds = dataset(fs, "/c")
    want = ds.scanner(format="pushdown", columns=["token"]).to_table()
    fs.store.fail_osd(0)
    fs.store.fail_osd(5)
    got = ds.scanner(format="pushdown", columns=["token"]).to_table()
    assert len(got) == len(want)
    print(f"  2/8 OSDs down, scan still returned {len(got)} rows\n")


def demo_hedging():
    print("=== 2. Straggler: hedged scan_op beats the tail ===")
    fs = make_cluster(8)
    corpus = synth_corpus(100, mean_doc_len=200, vocab_size=500, seed=2)
    write_corpus(fs, "/c", corpus, num_shards=4, row_group_rows=2048)
    ds = dataset(fs, "/c")
    # straggle the primary OSD of the first fragment
    frag = ds.fragments()[0]
    victim = fs.store.primary_of(fs.object_names(frag.path)[frag.obj_idx])
    victim.straggle_factor = 200.0
    sc = ds.scanner(format=PushdownParquetFormat(hedge_threshold_s=0.005),
                    columns=["token"])
    sc.to_table()
    hedged = sum(1 for t in sc.metrics.tasks if t.hedged)
    worst = max(t.cpu_s for t in sc.metrics.tasks)
    print(f"  {hedged} fragment(s) hedged to replicas; worst winning "
          f"task {worst * 1e3:.1f} ms\n")


def demo_checkpoint_restore():
    print("=== 3. Crash + restore from object-store checkpoint ===")
    fs = make_cluster(6)
    cm = CheckpointManager(fs, "/ckpt")
    state = {"params": {"w": jnp.arange(1e4).reshape(100, 100)},
             "step": jnp.array(41, jnp.int32)}
    cm.save(state, 41)
    hm = HealthMonitor(range(6), timeout_s=5.0)
    hm.mark_down(2)                                # "the node died"
    fs.store.fail_osd(2)
    structs = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    restored = cm.restore(structs)
    assert int(restored["step"]) == 41
    print(f"  dead hosts per heartbeat: {hm.dead_hosts()}; "
          f"state restored at step {int(restored['step'])} "
          "through degraded store\n")


def demo_elastic():
    print("=== 4. Elastic downsize: 8 devices -> lose 4 -> re-mesh ===")
    from repro.distrib import elastic_downsize
    from repro.launch.mesh import make_mesh
    from repro.sharding import default_rules, tree_shardings

    cpus = jax.devices("cpu")
    mesh = make_mesh((4, 2), ("data", "model"), devices=cpus[:8])
    rules = default_rules()
    state = {"w": np.arange(4096.0, dtype=np.float32).reshape(64, 64)}
    specs = {"w": ("embed", "mlp")}
    state = jax.device_put(state, tree_shardings(mesh, rules, state, specs))
    new_mesh, new_state, plan = elastic_downsize(
        state, specs, mesh, rules, cpus[:4])
    assert np.array_equal(np.asarray(new_state["w"]),
                          np.arange(4096.0).reshape(64, 64))
    print(f"  mesh {plan.old_shape} -> {plan.new_shape}, "
          f"state bitwise intact on {plan.devices_kept} devices")


if __name__ == "__main__":
    demo_failover()
    demo_hedging()
    demo_checkpoint_restore()
    demo_elastic()
    print("all fault-tolerance demos passed")
