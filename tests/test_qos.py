"""Multi-tenant QoS: weighted-fair admission, priority lanes, deadline
shedding, and per-tenant caching/metrics.

The grant policy itself (weighted fairness, lane priority, preemption)
is pinned deterministically against ``_OsdSlots`` — single-threaded
waiter-queue manipulation, no timing — and then the integrated stack
(registry -> query -> shared controller -> typed ``Shed``) is exercised
end to end, including the regression grid proving the default tenant
reproduces the historic single-tenant behavior at every layout x format
point.
"""

import threading
import time

import numpy as np
import pytest

from repro.aformat.expressions import field
from repro.core import (Shed, TaskContext, TenantRegistry, dataset,
                        make_cluster, write_flat, write_split,
                        write_striped)
from repro.dataset import MutableDataset, ResultCache
from repro.dataset.admission import (LANE_PRIORITY, AdmissionController,
                                     AdmissionTimeout, _OsdSlots, _Waiter)


@pytest.fixture
def flat_ds(taxi_table):
    fs = make_cluster(8)
    for i in range(4):
        write_flat(fs, f"/d/part{i}.arw", taxi_table.slice(i * 5000, 5000),
                   row_group_rows=1024)
    return fs, dataset(fs, "/d"), taxi_table


def _enqueue(slots: _OsdSlots, tenant: str, lane: str,
             weight: float) -> _Waiter:
    slots._seq += 1
    w = _Waiter(tenant, LANE_PRIORITY[lane], weight, slots._seq)
    slots.waiters.append(w)
    return w


def _drain_one(slots: _OsdSlots, holder: str, pending: list) -> _Waiter:
    """Release ``holder``'s slot; return the waiter the policy granted."""
    slots.release(holder)
    w = next(x for x in pending if x.granted)
    pending.remove(w)
    return w


# ---------------------------------------------------------------------------
# Grant policy (deterministic: no threads, no clocks)
# ---------------------------------------------------------------------------


def test_weighted_fair_split_converges_to_weights():
    """Under saturation the slot split converges to registered weights:
    tenant a (weight 3) takes exactly 3x the grants of b (weight 1)."""
    slots = _OsdSlots(slots=4, slack=0)
    held = ["a", "a", "a", "b"]          # seed at the fair split
    for t in held:
        slots._take(t)
    pending = [_enqueue(slots, "a", "bulk", 3.0),
               _enqueue(slots, "b", "bulk", 1.0)]
    grants = {"a": 0, "b": 0}
    for _ in range(200):
        w = _drain_one(slots, held.pop(0), pending)
        grants[w.tenant] += 1
        held.append(w.tenant)
        pending.append(_enqueue(slots, w.tenant, "bulk", w.weight))
    assert grants == {"a": 150, "b": 50}


def test_equal_weights_split_evenly():
    slots = _OsdSlots(slots=2, slack=0)
    held = ["a", "b"]
    for t in held:
        slots._take(t)
    pending = [_enqueue(slots, "a", "bulk", 1.0),
               _enqueue(slots, "b", "bulk", 1.0)]
    grants = {"a": 0, "b": 0}
    for _ in range(100):
        w = _drain_one(slots, held.pop(0), pending)
        grants[w.tenant] += 1
        held.append(w.tenant)
        pending.append(_enqueue(slots, w.tenant, "bulk", 1.0))
    assert grants == {"a": 50, "b": 50}


def test_lane_priority_orders_grants():
    """A freed slot never goes to a lane while a higher lane waits —
    and no weight can trump a lane."""
    slots = _OsdSlots(slots=1, slack=0)
    slots._take("warm")
    pending = [_enqueue(slots, "maint", "background", 100.0),
               _enqueue(slots, "etl", "bulk", 1.0),
               _enqueue(slots, "app", "interactive", 1.0)]
    order = []
    holder = "warm"
    for _ in range(3):
        w = _drain_one(slots, holder, pending)
        order.append(w.tenant)
        holder = w.tenant
    assert order == ["app", "etl", "maint"]


def test_compaction_waits_behind_interactive_grant():
    """The compaction lane never starves a foreground scan: with both a
    background and an interactive waiter queued, the freed slot always
    goes to the interactive waiter first."""
    slots = _OsdSlots(slots=1, slack=0)
    slots._take("warm")
    pending = [_enqueue(slots, "compaction", "background", 1.0),
               _enqueue(slots, "app", "interactive", 1.0)]
    assert _drain_one(slots, "warm", pending).tenant == "app"
    assert _drain_one(slots, "app", pending).tenant == "compaction"


def test_interactive_preempts_full_node():
    """An interactive arrival on a saturated node oversubscribes into the
    preempt slack instead of queueing behind bulk work."""
    slots = _OsdSlots(slots=1, slack=1)
    slots._take("etl")                      # node full
    _enqueue(slots, "etl", "bulk", 1.0)     # and a bulk waiter queued
    waited, preempted, wait_s = slots.acquire(
        "app", LANE_PRIORITY["interactive"], 1.0, lambda: None)
    assert (waited, preempted, wait_s) == (False, True, 0.0)
    assert slots.inflight == 2              # oversubscribed by the slack
    assert not slots.waiters[0].granted     # bulk still waits


def test_interactive_queues_behind_interactive():
    """Preemption slack is for jumping *lower* lanes only: a second
    interactive arrival queues FIFO behind the first."""
    slots = _OsdSlots(slots=1, slack=1)
    slots._take("app")
    _enqueue(slots, "app2", "interactive", 1.0)
    result = {}

    def acquire():
        result["r"] = slots.acquire(
            "app3", LANE_PRIORITY["interactive"], 1.0, lambda: None)

    t = threading.Thread(target=acquire)
    t.start()
    for _ in range(500):
        if len(slots.waiters) == 2:
            break
        time.sleep(0.002)
    assert len(slots.waiters) == 2          # app3 queued, no slack jump
    slots.release("app")                    # grants app2 (FIFO), not app3
    assert slots.by_tenant.get("app2") == 1
    slots.release("app2")
    t.join(5)
    waited, preempted, _ = result["r"]
    assert waited and not preempted


def test_controller_counts_preemptions():
    fs = make_cluster(2)
    ctrl = AdmissionController(fs.store, slots_per_osd=1, preempt_slack=1)
    app = TaskContext(tenant="app", lane="interactive")
    with ctrl.admit(0):                     # default bulk holds the slot
        with ctrl.admit(0, app):            # jumps in, does not block
            pass
    st = ctrl.stats()
    assert st["preemptions"] == 1
    assert st["by_tenant"]["app"]["preemptions"] == 1
    assert st["by_tenant"]["default"]["preemptions"] == 0


def test_admission_controller_records_wait_time():
    """The bugfix: ``wait_s`` (queue *time*) is recorded per acquisition,
    not just the blocked-or-not ``waits`` counter."""
    fs = make_cluster(2)
    ctrl = AdmissionController(fs.store, slots_per_osd=1)
    entered = threading.Event()
    release = threading.Event()

    def hold():
        with ctrl.admit(0):
            entered.set()
            release.wait(5)

    t = threading.Thread(target=hold)
    t.start()
    assert entered.wait(5)
    threading.Timer(0.05, release.set).start()
    with ctrl.admit(0):
        pass
    t.join(5)
    st = ctrl.stats()
    assert st["admitted"] == 2
    assert st["waits"] == 1
    assert st["wait_s"] >= 0.04             # actually measured queue time
    assert st["by_tenant"]["default"]["wait_s"] == \
        pytest.approx(st["wait_s"], abs=1e-5)


def test_deadline_expiry_in_queue_raises_admission_timeout():
    fs = make_cluster(2)
    ctrl = AdmissionController(fs.store, slots_per_osd=1)
    entered = threading.Event()
    release = threading.Event()

    def hold():
        with ctrl.admit(0):
            entered.set()
            release.wait(5)

    t = threading.Thread(target=hold)
    t.start()
    assert entered.wait(5)
    ctx = TaskContext(tenant="late", deadline_s=0.03,
                      started_at=time.perf_counter())
    try:
        with pytest.raises(AdmissionTimeout):
            with ctrl.admit(0, ctx):
                pass
    finally:
        release.set()
        t.join(5)
    st = ctrl.stats()
    assert st["sheds"] == 1
    assert st["by_tenant"]["late"]["sheds"] == 1
    assert st["by_tenant"]["late"]["wait_s"] >= 0.02
    assert st["by_tenant"]["late"]["admitted"] == 0


# ---------------------------------------------------------------------------
# Registry + query integration
# ---------------------------------------------------------------------------


def test_tenant_tagged_query_and_rollup(flat_ds):
    fs, ds, tbl = flat_ds
    reg = TenantRegistry()
    reg.register("app", weight=4.0, lane="interactive")
    reg.register("etl", weight=1.0, lane="bulk")

    qa = ds.query(tenant=reg.context("app")).filter(
        field("fare_amount") > 30.0)
    out = qa.to_table()
    expect = int((tbl.column("fare_amount").values > 30.0).sum())
    assert len(out) == expect
    assert qa.metrics.tenant == "app"
    assert qa.metrics.lane == "interactive"
    s = qa.metrics.summary()
    assert s["tenant"] == "app" and s["lane"] == "interactive"
    for k in ("admission_wait_s", "preemptions", "sheds"):
        assert k in s

    # filtered: a bare COUNT(*) is metadata-answered, no storage work
    qe = ds.query(tenant=reg.context("etl")).filter(
        field("fare_amount") > 30.0).count()
    assert qe.to_scalar() == expect
    assert qe.metrics.tenant == "etl" and qe.metrics.lane == "bulk"

    by = reg.by_tenant()
    assert by["app"]["runs"] == 1 and by["etl"]["runs"] == 1
    assert by["app"]["rows"] == expect
    assert by["app"]["admission"]["admitted"] == len(qa.metrics.tasks)
    assert by["etl"]["admission"]["admitted"] == len(qe.metrics.tasks)


def test_scan_metrics_surface_wait_time(flat_ds):
    fs, ds, _ = flat_ds
    q = ds.query(format="pushdown", num_threads=16,
                 queue_depth=1).select("trip_id")
    q.to_table()
    adm = q.metrics.admission
    assert adm["admitted"] == len(q.metrics.tasks)
    assert "wait_s" in adm and "preemptions" in adm and "sheds" in adm
    if adm["waits"]:
        assert adm["wait_s"] > 0.0


def test_explain_shows_tenant_lane_deadline(flat_ds):
    _, ds, _ = flat_ds
    reg = TenantRegistry()
    reg.register("app", lane="interactive")
    txt = ds.query(tenant=reg.context("app", deadline_s=0.5)).explain()
    assert "tenant=app/interactive" in txt
    assert "deadline=500ms/reject" in txt
    # the default tenant keeps the historic executor line
    assert "tenant=" not in ds.query().explain()


def test_deadline_shed_is_typed_and_deterministic(flat_ds):
    """An impossible deadline under injected straggle sheds every time —
    as a typed Shed result, never an exception from a worker thread."""
    fs, ds, _ = flat_ds
    for osd in fs.store.osds:
        osd.straggle_factor = 40.0          # every storage call is slow
    reg = TenantRegistry()
    reg.register("app", lane="interactive", deadline_s=1e-4)
    for _ in range(3):
        q = ds.query(tenant=reg.context("app"), num_threads=1)
        out = q.to_table()
        assert isinstance(out, Shed)
        assert out.tenant == "app" and out.lane == "interactive"
        assert out.completed_tasks < out.total_tasks
        assert out.partial is None          # reject policy
        assert q.metrics.shed is out
        assert "shed" in q.metrics.summary()
    assert reg.by_tenant()["app"]["sheds"] == 3


def test_shed_retry_is_byte_identical(flat_ds):
    """A shed query retried without the deadline returns exactly the
    bytes a never-shed control run returns."""
    fs, ds, _ = flat_ds
    reg = TenantRegistry()
    reg.register("app", lane="interactive")
    pred = field("passenger_count") > 3
    control = (ds.query(tenant=reg.context("app")).filter(pred)
               .to_table())
    shed = (ds.query(tenant=reg.context("app", deadline_s=1e-9),
                     num_threads=1).filter(pred).to_table())
    assert isinstance(shed, Shed)
    retry = (ds.query(tenant=reg.context("app")).filter(pred)
             .to_table())
    assert retry.to_ipc() == control.to_ipc()


def test_degrade_policy_attaches_partial(flat_ds):
    fs, ds, _ = flat_ds
    for osd in fs.store.osds:
        osd.straggle_factor = 40.0
    reg = TenantRegistry()
    reg.register("dash", lane="interactive", deadline_s=0.1,
                 shed_policy="degrade")
    q = ds.query(tenant=reg.context("dash"), num_threads=1).select(
        "trip_id")
    out = q.to_table()
    assert isinstance(out, Shed)
    assert out.partial is not None
    assert len(out.partial) == sum(t.rows_out for t in q.metrics.tasks)
    assert out.completed_tasks < out.total_tasks


def test_scalar_shed_has_no_partial(flat_ds):
    """Aggregates never degrade: a partial aggregate is a wrong answer."""
    fs, ds, _ = flat_ds
    for osd in fs.store.osds:
        osd.straggle_factor = 40.0
    reg = TenantRegistry()
    reg.register("dash", lane="interactive", deadline_s=1e-4,
                 shed_policy="degrade")
    # filtered so the count needs storage tasks (not metadata-answered)
    out = (ds.query(tenant=reg.context("dash"), num_threads=1)
           .filter(field("fare_amount") > 30.0).count().to_scalar())
    assert isinstance(out, Shed)
    assert out.partial is None


def test_compaction_runs_as_background_tenant(taxi_table):
    """compact() rides the background lane through the registry's shared
    controller, and foreground scans against the same cluster complete
    correctly afterwards."""
    fs = make_cluster(4)
    md = MutableDataset.create(fs, "/mut")
    for i in range(6):
        md.append(taxi_table.slice(i * 1000, 1000))
    reg = TenantRegistry(slots_per_osd=2)
    reg.register("app", weight=4.0, lane="interactive")
    reg.register("compaction", lane="background")

    report = md.compact(tenant=reg.context("compaction"))
    assert report.groups > 0
    by = reg.by_tenant()
    assert by["compaction"]["admission"]["admitted"] >= 1

    out = md.query(tenant=reg.context("app")).to_table()
    assert len(out) == 6000
    assert reg.by_tenant()["app"]["runs"] == 1


# ---------------------------------------------------------------------------
# Per-tenant result cache
# ---------------------------------------------------------------------------


def test_cache_bulk_cannot_evict_interactive_working_set():
    cache = ResultCache(capacity_bytes=4096)
    cache.put(("hot", 1), b"x" * 512, tenant="app", budget=1024)
    for i in range(64):
        cache.put(("cold", i), b"y" * 1024, tenant="etl", budget=2048)
    assert cache.contains(("hot", 1), tenant="app")
    by = cache.by_tenant()
    assert by["etl"]["bytes"] <= 2048
    assert by["app"]["bytes"] == 512


def test_cache_budget_bounds_own_shard_lru():
    cache = ResultCache(capacity_bytes=1 << 20)
    for i in range(10):
        cache.put(("k", i), b"z" * 100, tenant="t", budget=350)
    assert cache.by_tenant()["t"]["bytes"] <= 350
    # LRU within the shard: the newest entries survive
    assert cache.contains(("k", 9), tenant="t")
    assert not cache.contains(("k", 0), tenant="t")


def test_cache_default_tenant_matches_historic_behavior():
    cache = ResultCache(capacity_bytes=1000)
    for i in range(5):
        cache.put(("k", i), b"a" * 300)
    assert len(cache) == 3                  # 900 bytes fit; LRU evicted 2
    assert cache.get(("k", 4)) == b"a" * 300
    assert cache.get(("k", 0)) is None
    st = cache.stats()
    assert st["evictions"] == 2
    assert set(st) == {"entries", "bytes", "hits", "misses", "evictions"}


def test_cache_entries_are_tenant_scoped():
    cache = ResultCache()
    cache.put(("k",), b"v", tenant="a")
    assert cache.get(("k",), tenant="b") is None
    assert cache.get(("k",), tenant="a") == b"v"
    assert cache.contains(("k",))           # any-tenant probe


# ---------------------------------------------------------------------------
# Single-tenant regression grid: default tenant == historic behavior
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("layout", ["flat", "split", "striped"])
@pytest.mark.parametrize("fmt", ["parquet", "pushdown", "adaptive"])
def test_single_tenant_grid_unchanged(taxi_table, layout, fmt):
    fs = make_cluster(8)
    writer = {"flat": write_flat, "split": write_split,
              "striped": write_striped}[layout]
    sub = taxi_table.slice(0, 4000)
    writer(fs, "/g/part0.arw", sub, row_group_rows=1000)
    ds = dataset(fs, "/g")
    pred = field("fare_amount") > 25.0
    q = ds.query(format=fmt).filter(pred).select("trip_id")
    out = q.to_table()
    expect = sub.column("trip_id").values[
        sub.column("fare_amount").values > 25.0]
    assert np.array_equal(np.sort(out.column("trip_id").values),
                          np.sort(expect))
    assert q.metrics.tenant == "default"
    assert q.metrics.shed is None
    n = ds.query(format=fmt).filter(pred).count().to_scalar()
    assert n == len(expect)
