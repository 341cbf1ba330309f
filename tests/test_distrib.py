"""Fault tolerance: checkpoint round-trips, health, elastic downsize."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

# slow lane: jax/pallas compile-heavy; skipped by `make test-fast` / CI per-push
pytestmark = pytest.mark.slow

from repro.core import make_cluster
from repro.distrib import (CheckpointManager, HealthMonitor,
                           InsufficientDevicesError, plan_downsize)


@pytest.fixture
def state():
    return {
        "params": {"w": jnp.arange(24.0).reshape(4, 6),
                   "b": jnp.full((6,), 0.5),
                   "scan": jnp.ones((3, 2, 2))},
        "opt": {"m": jnp.zeros((4, 6)), "count": jnp.array(3, jnp.int32)},
        "step": jnp.array(17, jnp.int32),
    }


def _structs(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        tree)


def test_checkpoint_roundtrip(state):
    fs = make_cluster(4)
    cm = CheckpointManager(fs, "/ck")
    cm.save(state, 17)
    out = cm.restore(_structs(state))
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(out)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_gc_keeps_last_k(state):
    fs = make_cluster(4)
    cm = CheckpointManager(fs, "/ck", keep=2)
    for s in (1, 2, 3, 4):
        cm.save(state, s)
    assert cm.steps() == [3, 4]
    # old files actually deleted from the store
    assert not [p for p in fs.listdir("/ck") if "step_0000000001" in p]


def test_checkpoint_crc_detects_corruption(state):
    fs = make_cluster(4)
    cm = CheckpointManager(fs, "/ck")
    m = cm.save(state, 1)
    victim = m["leaves"][0]["file"]
    ino = fs.stat(victim)
    name = fs.object_name(ino, 0)
    for osd in fs.store.acting_set(name):      # corrupt every replica
        if osd.contains(name):
            osd._objects[name] = b"\x00" * len(osd._objects[name])
    with pytest.raises(IOError, match="CRC"):
        cm.restore(_structs(state))


def test_checkpoint_async(state):
    fs = make_cluster(4)
    cm = CheckpointManager(fs, "/ck")
    cm.save_async(state, 5)
    cm.wait()
    assert cm.latest_step() == 5
    out = cm.restore(_structs(state), 5)
    assert np.asarray(out["step"]) == 17


def test_checkpoint_survives_osd_loss(state):
    fs = make_cluster(6)
    cm = CheckpointManager(fs, "/ck")
    cm.save(state, 9)
    fs.store.fail_osd(0)
    fs.store.fail_osd(1)
    out = cm.restore(_structs(state))
    assert np.array_equal(np.asarray(out["params"]["w"]),
                          np.asarray(state["params"]["w"]))


def test_restore_missing_leaf_raises(state):
    fs = make_cluster(4)
    cm = CheckpointManager(fs, "/ck")
    cm.save(state, 1)
    bigger = dict(state, extra=jnp.zeros(3))
    with pytest.raises(KeyError):
        cm.restore(_structs(bigger))


# ---------------------------------------------------------------------------
# health + downsize planning
# ---------------------------------------------------------------------------


def test_health_monitor_timeout():
    hm = HealthMonitor(range(4), timeout_s=10.0)
    t0 = 1000.0
    for h in range(4):
        hm.heartbeat(h, now=t0)
    assert hm.dead_hosts(now=t0 + 5) == []
    hm.heartbeat(0, now=t0 + 12)
    hm.heartbeat(1, now=t0 + 12)
    assert hm.dead_hosts(now=t0 + 12) == [2, 3]
    assert hm.healthy_hosts(now=t0 + 12) == [0, 1]


def test_health_mark_down_and_rejoin():
    hm = HealthMonitor(range(3), timeout_s=1e9)
    hm.mark_down(1)
    hm.heartbeat(1)              # ignored while marked down
    assert 1 in hm.dead_hosts()
    hm.rejoin(1)
    assert hm.dead_hosts() == []


def test_plan_downsize_shrinks_data_axis_pow2():
    # fabricate shape arithmetic via a stand-in object
    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    plan = plan_downsize(FakeMesh(), 16 * 13)
    assert plan.new_shape == (8, 16)            # floor-pow2 of 13
    plan = plan_downsize(FakeMesh(), 16 * 16)
    assert not plan.changed
    with pytest.raises(InsufficientDevicesError):
        plan_downsize(FakeMesh(), 7)


def test_elastic_downsize_end_to_end_subprocess():
    """Real 8-device resharding (device count needs its own process)."""
    import textwrap

    from subproc import run_python

    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.distrib import elastic_downsize
        from repro.launch.mesh import make_mesh
        from repro.sharding import default_rules
        mesh = make_mesh((4, 2), ("data", "model"))
        rules = default_rules()
        state = {"w": jnp.arange(64.0).reshape(8, 8)}
        specs = {"w": ("embed", "mlp")}
        from repro.sharding import tree_shardings
        state = jax.device_put(state, tree_shardings(mesh, rules, state,
                                                     specs))
        healthy = list(jax.devices())[:4]       # lost half the fleet
        new_mesh, new_state, plan = elastic_downsize(
            state, specs, mesh, rules, healthy)
        assert plan.new_shape == (2, 2), plan
        assert np.array_equal(np.asarray(new_state["w"]),
                              np.arange(64.0).reshape(8, 8))
        ns = new_state["w"].sharding
        assert ns.mesh.shape["data"] == 2
        print("ELASTIC_OK")
    """)
    out = run_python(["-c", code])
    assert "ELASTIC_OK" in out.stdout, out.stderr[-2000:]
