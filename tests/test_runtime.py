"""Property tests for the multi-tenant switch runtime (``repro.runtime``).

All parent-side (pure control-plane Python — the tensor-level bitwise
isolation claim runs on the 8-device mesh in
``tests/multidevice_checks.py`` group ``runtime``):

* **Partition policies** — hypothesis invariants: ``weighted_fair``
  allocations sum to exactly the cluster total with ≥ 1 cluster per
  session, ``greedy`` is work-conserving (no idle cluster while any
  session has queued packets), ``static`` honors the §4 predefined
  maximum, all slices disjoint.
* **Scheduler** — round-robin prefix fairness, strict priority
  ordering, and counter *conservation*: per-tenant combine counters in
  a shared schedule sum to the single-tenant totals (the interleave
  reorders work, it never creates or destroys any).
* **Admission control** — session count, HPU clusters, and the static
  aggregation-buffer memory share from ``perfmodel.switch_model``.
* **Model cross-check** — the shared-switch mode's per-tenant
  throughput predictions (``switch_model.model_shared``) agree with the
  scheduler's measured counters within the tolerance
  ``tests/test_switch.py`` uses for the single-job cross-checks.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.perfmodel import switch_model as sm
from repro.runtime import (AdmissionError, SessionManager, TenantLoad,
                           greedy_partition, ingress_shares, interleave,
                           make_partition, session_demand_bytes,
                           simulate_shared, static_partition,
                           weighted_fair_partition)
from repro.switch import dataplane

# -- strategies -------------------------------------------------------------

_weights = st.dictionaries(
    st.sampled_from([f"t{i}" for i in range(8)]),
    st.floats(0.1, 10.0, allow_nan=False), min_size=1, max_size=8)


# ---------------------------------------------------------------------------
# Partition policies.
# ---------------------------------------------------------------------------

@given(_weights, st.integers(8, 128))
@settings(max_examples=60, deadline=None)
def test_weighted_fair_sums_to_total_and_min_one(weights, clusters):
    part = weighted_fair_partition(weights, clusters)
    part.validate()
    assert part.allocated == clusters            # exactly conserved
    assert all(part.clusters(t) >= 1 for t in weights)
    # heavier sessions never get fewer clusters than much lighter ones
    # off by more than the rounding quantum
    for a in weights:
        for b in weights:
            if weights[a] >= weights[b]:
                assert part.clusters(a) >= part.clusters(b) - 1


@given(_weights, st.integers(8, 64), st.data())
@settings(max_examples=60, deadline=None)
def test_greedy_is_work_conserving(weights, clusters, data):
    queued = {t: data.draw(st.integers(0, 5), label=f"queued[{t}]")
              for t in weights}
    part = greedy_partition(weights, clusters, queued)
    part.validate()
    busy = [t for t in weights if queued[t] > 0]
    if busy:
        # no idle cluster while any session has queued packets, and
        # idle sessions hold nothing
        assert sum(part.clusters(t) for t in busy) == clusters
        for t in weights:
            if queued[t] == 0:
                assert part.clusters(t) == 0
    else:
        # nothing queued anywhere → fair shares stand ready
        assert part.allocated == clusters


@given(_weights, st.integers(16, 128), st.integers(8, 16))
@settings(max_examples=40, deadline=None)
def test_static_partition_shares(weights, clusters, max_sessions):
    part = static_partition(weights, clusters, max_sessions)
    part.validate()
    per = clusters // max_sessions
    assert all(part.clusters(t) == per for t in weights)


def test_partition_dispatch_and_errors():
    with pytest.raises(ValueError, match="unknown partition policy"):
        make_partition("fifo", {"a": 1.0}, 8)
    with pytest.raises(ValueError, match="max_sessions"):
        make_partition("static", {"a": 1.0}, 8)
    with pytest.raises(ValueError, match="one each"):
        weighted_fair_partition({"a": 1.0, "b": 1.0, "c": 1.0}, 2)
    with pytest.raises(ValueError, match="positive"):
        weighted_fair_partition({"a": 0.0}, 8)
    with pytest.raises(ValueError, match="exceed"):
        static_partition({f"t{i}": 1.0 for i in range(3)}, 16,
                         max_sessions=2)


# ---------------------------------------------------------------------------
# Scheduler: interleave shape and counter conservation.
# ---------------------------------------------------------------------------

@given(st.dictionaries(st.sampled_from(["a", "b", "c", "d"]),
                       st.integers(0, 40), min_size=1, max_size=4))
@settings(max_examples=60, deadline=None)
def test_round_robin_interleave_is_prefix_fair(packets):
    seq = interleave(packets, "round_robin")
    assert len(seq) == sum(packets.values())
    # per-tenant indices appear in order, and any prefix serves active
    # tenants within one packet of each other
    seen = {t: 0 for t in packets}
    for t, i in seq:
        assert i == seen[t]
        seen[t] += 1
        active = [u for u in packets if seen[u] < packets[u]]
        if active:
            lo = min(seen[u] for u in active)
            hi = max(seen[u] for u in active)
            assert hi - lo <= 1
    assert seen == {t: n for t, n in packets.items()}


def test_priority_interleave_drains_high_first():
    seq = interleave({"lo": 3, "hi": 2, "mid": 1}, "priority",
                     priorities={"lo": 0, "hi": 9, "mid": 5})
    assert [t for t, _ in seq] == ["hi", "hi", "mid", "lo", "lo", "lo"]
    with pytest.raises(ValueError, match="unknown schedule order"):
        interleave({"a": 1}, "lifo")


def _load(tenant, *, b=2, s=2048, clusters=8, priority=0, mode_dtype=None,
          tree_sizes=(8,)):
    from repro.core import topology
    tree = topology.build_mesh_tree(tree_sizes)
    counters = dataplane.tree_counters(tree, b, s,
                                       mode_dtype or jnp.float32)
    return TenantLoad(tenant=tenant, counters=counters, clusters=clusters,
                      priority=priority)


@given(st.integers(1, 4), st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_shared_counters_conserve_single_tenant_totals(n_tenants, seed):
    """The interleave reorders work, it never creates or destroys any:
    per-tenant packet/combine counters in the shared schedule equal the
    tenant's solo totals, and the shared sums equal the sum of solos."""
    rng = np.random.default_rng(seed)
    loads = [_load(f"t{i}", b=int(rng.integers(1, 4)),
                   s=int(rng.integers(1, 9)) * 512)
             for i in range(n_tenants)]
    shared = simulate_shared(loads)
    solos = [simulate_shared([l]) for l in loads]
    for l, solo in zip(loads, solos):
        sc_shared = shared.tenant(l.tenant)
        sc_solo = solo.tenant(l.tenant)
        assert sc_shared.packets == sc_solo.packets == l.leaf_packets
        assert sc_shared.combines == sc_solo.combines == l.combines
        assert sc_shared.occupancy_cycles == sc_solo.occupancy_cycles
    assert (sum(c.combines for c in shared.counters)
            == sum(s.counters[0].combines for s in solos))
    assert (sum(c.packets for c in shared.counters)
            == len(shared.order))


def test_simulate_shared_rejects_non_work_conserving_partition():
    loads = [_load("busy", clusters=0)]
    with pytest.raises(ValueError, match="work-conserving"):
        simulate_shared(loads)


def test_schedule_with_partial_backlog_under_greedy():
    """A queued snapshot drives BOTH the greedy reclamation and the
    simulated packet counts: an idle tenant gets 0 clusters and 0
    scheduled packets (no spurious work-conserving error)."""
    mgr = SessionManager(("pod", "data"), (2, 4), policy="greedy",
                         max_sessions=4)
    mgr.open("a", mode="dense", num_buckets=2, bucket_elems=256,
             dtype=jnp.float32)
    mgr.open("b", mode="dense", num_buckets=2, bucket_elems=256,
             dtype=jnp.float32)
    sched = mgr.schedule(queued={"a": 0, "b": 10})
    assert sched.tenant("a").packets == 0
    assert sched.tenant("a").throughput_pkts == 0.0
    assert sched.tenant("b").packets == 10
    assert sched.tenant("b").throughput_pkts > 0
    assert all(t == "b" for t, _ in sched.order)


def test_ingress_shares_round_robin_window_math():
    # equal counts → equal shares; a small tenant's window share is the
    # per-round fair 1/n, not its global packet fraction
    assert ingress_shares({"a": 10, "b": 10}) == {"a": 0.5, "b": 0.5}
    sh = ingress_shares({"a": 4096, "b": 512})
    assert sh["b"] == pytest.approx(512 / (512 + 512))
    assert sh["a"] == pytest.approx(4096 / (4096 + 512))
    assert ingress_shares({"a": 1, "b": 2}, "priority") == \
        {"a": 1.0, "b": 1.0}


# ---------------------------------------------------------------------------
# Admission control.
# ---------------------------------------------------------------------------

def _mgr(**kw):
    kw.setdefault("max_sessions", 4)
    return SessionManager(("pod", "data"), (2, 4), **kw)


def test_admission_session_count_and_close():
    mgr = _mgr(max_sessions=2)
    mgr.open("a", mode="dense", num_buckets=1, bucket_elems=256,
             dtype=jnp.float32)
    mgr.open("b", mode="int8", num_buckets=1, bucket_elems=256,
             dtype=jnp.float32)
    with pytest.raises(AdmissionError, match="predefined maximum"):
        mgr.open("c", mode="dense", num_buckets=1, bucket_elems=256,
                 dtype=jnp.float32)
    mgr.close("a")
    mgr.open("c", mode="dense", num_buckets=1, bucket_elems=256,
             dtype=jnp.float32)
    with pytest.raises(ValueError, match="already open"):
        mgr.open("c", mode="dense", num_buckets=1, bucket_elems=256,
                 dtype=jnp.float32)


def test_admission_memory_share():
    """The §4 static memory split: a session whose aggregation-buffer
    working set exceeds L1_total / max_sessions is rejected."""
    params = sm.SwitchParams(clusters=2, l1_bytes_per_cluster=64 << 10)
    mgr = _mgr(params=params, max_sessions=4)
    with pytest.raises(AdmissionError, match="aggregation"):
        mgr.open("big", mode="dense", num_buckets=64, bucket_elems=4096,
                 dtype=jnp.float32)
    small = mgr.open("small", mode="dense", num_buckets=1,
                     bucket_elems=256, dtype=jnp.float32)
    assert small.demand_bytes <= mgr.bytes_per_session
    # demand follows the switch_model working-memory multiplier M
    c = small.counters
    m = max(l.buffers_per_block for l in c.levels)
    assert session_demand_bytes(c) == \
        int(np.ceil(m * c.blocks)) * c.packet_bytes
    assert m == sm.buffers_per_block(c.design, c.levels[0].fanin, c.n_bufs)


def test_static_policy_capacity_checked_at_construction():
    """clusters < max_sessions under the static policy would give every
    session a 0-cluster share — refused up front, not at first report."""
    with pytest.raises(ValueError, match="static policy"):
        _mgr(params=sm.SwitchParams(clusters=4), policy="static",
             max_sessions=8)
    ok = _mgr(params=sm.SwitchParams(clusters=8), policy="static",
              max_sessions=4)
    ok.open("a", mode="dense", num_buckets=1, bucket_elems=256,
            dtype=jnp.float32)
    assert ok.partition().clusters("a") == 2


def test_admission_cluster_floor():
    params = sm.SwitchParams(clusters=1)
    mgr = _mgr(params=params, max_sessions=8)
    mgr.open("a", mode="dense", num_buckets=1, bucket_elems=256,
             dtype=jnp.float32)
    with pytest.raises(AdmissionError, match="HPU clusters"):
        mgr.open("b", mode="dense", num_buckets=1, bucket_elems=256,
                 dtype=jnp.float32)


def test_attach_reuses_matching_spec_and_readmits_changed():
    mgr = _mgr()
    s1 = mgr.attach("t", mode="dense", num_buckets=2, bucket_elems=256,
                    dtype=jnp.float32)
    s2 = mgr.attach("t", mode="dense", num_buckets=2, bucket_elems=256,
                    dtype=jnp.float32)
    assert s1 is s2                                # re-trace → same session
    s3 = mgr.attach("t", mode="dense", num_buckets=4, bucket_elems=512,
                    dtype=jnp.float32)
    assert s3.spec != s1.spec and len(mgr.active()) == 1
    # anonymous attaches would collapse distinct same-shape jobs into
    # one tenant (no contention modeled) — they must be refused
    with pytest.raises(ValueError, match="tenant name"):
        mgr.attach(None, mode="int8", num_buckets=1, bucket_elems=256,
                   dtype=jnp.float32)
    assert mgr.new_tenant() != mgr.new_tenant()    # reducer auto-names
    with pytest.raises(ValueError, match="axes"):
        mgr.attach("t", mode="dense", num_buckets=2, bucket_elems=256,
                   dtype=jnp.float32, axes=("data",))


def test_attach_readmits_on_changed_k_or_design():
    """The reuse key covers everything admission depends on: a changed
    sparse k (wire image) or aggregation design (memory multiplier M)
    must re-run admission, not reuse the stale session's demand."""
    mgr = _mgr()
    s1 = mgr.attach("sp", mode="sparse", num_buckets=2, bucket_elems=4096,
                    dtype=jnp.float32, k=16)
    s2 = mgr.attach("sp", mode="sparse", num_buckets=2, bucket_elems=4096,
                    dtype=jnp.float32, k=1024)
    assert s2 is not s1 and len(mgr.active()) == 1
    assert s2.demand_bytes > s1.demand_bytes       # re-admitted, not stale
    d1 = mgr.attach("d", mode="dense", num_buckets=1, bucket_elems=256,
                    dtype=jnp.float32, reproducible=False)
    d2 = mgr.attach("d", mode="dense", num_buckets=1, bucket_elems=256,
                    dtype=jnp.float32, reproducible=True)
    assert d2 is not d1 and d2.counters.design == "tree"


def test_arrival_perms_solo_none_shared_deterministic():
    mgr = _mgr(seed=3)
    mgr.open("a", mode="dense", num_buckets=2, bucket_elems=256,
             dtype=jnp.float32)
    assert mgr.arrival_perms("a") is None          # idle switch
    mgr.open("b", mode="sparse", num_buckets=1, bucket_elems=512,
             dtype=jnp.float32, k=16)
    perms = mgr.arrival_perms("a")
    assert len(perms) == 2                         # one per mesh level
    p0 = perms[0](4, 5)
    assert p0.shape == (4, 5)
    for col in p0.T:
        assert sorted(col) == [0, 1, 2, 3]         # valid per-slot perms
    # deterministic across calls, distinct across tenants and epochs
    assert np.array_equal(p0, mgr.arrival_perms("a")[0](4, 5))
    assert not np.array_equal(p0, mgr.arrival_perms("b")[0](4, 5))
    mgr.rebind(mgr.tree)
    assert not np.array_equal(p0, mgr.arrival_perms("a")[0](4, 5))
    with pytest.raises(KeyError):
        mgr.arrival_perms("nope")


# ---------------------------------------------------------------------------
# Shared-switch model ↔ scheduler cross-check (the runtime's half of the
# test_switch.py emulator ↔ model pinning; same tolerance style).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("order", ["round_robin", "priority"])
@pytest.mark.parametrize("policy", ["weighted_fair", "static", "greedy"])
def test_shared_model_matches_scheduler_throughput(order, policy):
    mgr = SessionManager(("pod", "data"), (2, 4), policy=policy,
                         order=order)
    mgr.open("dense", mode="dense", num_buckets=8, bucket_elems=1 << 15,
             dtype=jnp.float32, priority=2)
    mgr.open("int8", mode="int8", num_buckets=8, bucket_elems=1 << 15,
             dtype=jnp.float32, priority=1)
    mgr.open("sparse", mode="sparse", num_buckets=8, bucket_elems=1 << 15,
             dtype=jnp.float32, k=2048)
    sched = mgr.schedule()
    pred = {p.tenant: p for p in mgr.predicted()}
    for c in sched.counters:
        p = pred[c.tenant]
        assert p.bandwidth_pkts > 0 and p.bandwidth_tbps > 0
        assert 0.5 * p.bandwidth_pkts < c.throughput_pkts \
            < 1.8 * p.bandwidth_pkts, \
            (policy, order, c.tenant, c.throughput_pkts, p.bandwidth_pkts)


def test_model_shared_bottleneck_split():
    params = sm.SwitchParams()
    # plenty of clusters → line-bound at its share; one cluster → compute
    pts = sm.model_shared([("fat", 32, 1024.0, 0.1),
                           ("thin", 1, 1024.0, 0.9)], params)
    by = {p.tenant: p for p in pts}
    assert by["fat"].bottleneck == "line"
    assert by["fat"].bandwidth_pkts == pytest.approx(0.1 / params.delta)
    assert by["thin"].bottleneck == "compute"
    assert by["thin"].bandwidth_pkts == pytest.approx(
        params.cores_per_cluster / 1024.0)
    # a reclaimed tenant (0 clusters) predicts zero throughput
    (idle,) = sm.model_shared([("idle", 0, 1024.0, 0.0)], params)
    assert idle.bandwidth_pkts == 0.0


# ---------------------------------------------------------------------------
# Rebind / report plumbing.
# ---------------------------------------------------------------------------

def test_tree_counters_matches_plan_counters_on_mesh_trees():
    """On a plain mesh tree the two counter paths agree level by level —
    the rebind path and the PR 4 cross-check path cannot drift."""
    from repro.core import topology
    for sizes in [(8,), (2, 4), (4, 2)]:
        names = ("pod", "data")[-len(sizes):]
        a = dataplane.plan_counters(names, sizes, 3, 2048, jnp.float32)
        b = dataplane.tree_counters(topology.build_mesh_tree(sizes),
                                    3, 2048, jnp.float32)
        assert (a.blocks, a.design, a.n_bufs) == (b.blocks, b.design,
                                                  b.n_bufs)
        assert [(l.fanin, l.ingress_packets, l.combines,
                 l.buffers_per_block) for l in a.levels] == \
            [(l.fanin, l.ingress_packets, l.combines,
              l.buffers_per_block) for l in b.levels]


def test_report_names_every_session():
    mgr = _mgr()
    assert "idle" in str(mgr.report())
    mgr.open("a", mode="dense", num_buckets=1, bucket_elems=256,
             dtype=jnp.float32)
    mgr.open("b", mode="sparse", num_buckets=1, bucket_elems=512,
             dtype=jnp.float32, k=8)
    rep = str(mgr.report())
    assert "a:" in rep and "b:" in rep and "predicted" in rep


# ---------------------------------------------------------------------------
# Lossy sessions: retransmission demand reaches the shared scheduler.
# ---------------------------------------------------------------------------

def test_lossy_session_schedules_retransmit_demand():
    """Regression: ``_loads`` used to drop ``Session.retransmit_packets``
    on the floor — a lossy tenant's modeled service demand silently
    equalled the fault-free one.  The fault plan's static retransmit
    count must reach the scheduled packets, the partition's queued view
    and the analytic prediction alike."""
    from repro.switch.packets import FaultPlan
    kw = dict(mode="dense", num_buckets=4, bucket_elems=256,
              dtype=jnp.float32)
    clean = _mgr()
    clean.open("t", **kw)
    lossy = _mgr()
    lossy.open("t", **kw, fault_plan=FaultPlan(seed=1, drop=0.2))
    sess = lossy.session("t")
    assert sess.retransmit_packets > 0
    base = clean.session("t").counters.levels[0].ingress_packets
    assert lossy.schedule().tenant("t").packets \
        == base + sess.retransmit_packets \
        > clean.schedule().tenant("t").packets
    # steady-state queued view includes the retransmissions too
    assert lossy.partition() is not None     # no work-conserving error
    assert lossy.schedule(queued={"t": 5}).tenant("t").packets \
        == 5 + sess.retransmit_packets


def test_attach_reopens_on_changed_fault_plan():
    """Same wire spec but a different fault plan is a *different*
    session: the retransmit demand must be recomputed."""
    from repro.switch.packets import FaultPlan
    mgr = _mgr()
    kw = dict(mode="dense", num_buckets=4, bucket_elems=256,
              dtype=jnp.float32)
    a = mgr.attach("t", **kw)
    assert a.retransmit_packets == 0
    b = mgr.attach("t", **kw, fault_plan=FaultPlan(seed=1, drop=0.2))
    assert b.retransmit_packets > 0
    assert mgr.attach("t", **kw,
                      fault_plan=FaultPlan(seed=1, drop=0.2)) is b


def test_rebind_preserves_fault_plan():
    """The failure path re-opens sessions with their fault plans: a
    lossy tenant's retransmit demand survives the rebind (recomputed on
    the new tree, not silently zeroed)."""
    from repro.core import topology
    from repro.switch.packets import FaultPlan
    mgr = _mgr()
    mgr.open("t", mode="dense", num_buckets=4, bucket_elems=256,
             dtype=jnp.float32, fault_plan=FaultPlan(seed=1, drop=0.2))
    readmitted, evicted = mgr.rebind(topology.build_tree(8, 4))
    assert readmitted == ("t",) and not evicted
    sess = mgr.session("t")
    assert sess.fault_plan is not None
    assert sess.retransmit_packets > 0


# ---------------------------------------------------------------------------
# Congestion-aware replanning (DESIGN.md §15).
# ---------------------------------------------------------------------------

def _open_two(mgr):
    mgr.open("a", mode="dense", num_buckets=2, bucket_elems=256,
             dtype=jnp.float32, reproducible=True)
    mgr.open("b", mode="sparse", num_buckets=2, bucket_elems=512,
             dtype=jnp.float32, k=16)


def test_replan_below_threshold_is_noop():
    mgr = _mgr()
    _open_two(mgr)
    res = mgr.replan(hotness={(1, 0): 0.3}, threshold=0.5)
    assert not res.replanned and res.reason == "below threshold"
    assert res.predicted_after == res.predicted_before
    assert mgr._epoch == 0 and res.improvement_x == 1.0


def test_replan_routes_around_hot_slot():
    mgr = _mgr()
    _open_two(mgr)
    old_nodes = mgr.tree.nodes
    res = mgr.replan(hotness={(1, 0): 2.0}, threshold=0.5)
    assert res.replanned and res.reason == "replanned"
    assert mgr.tree.nodes != old_nodes
    assert mgr._epoch == 1                       # fresh arrival perms
    assert sorted(res.readmitted) == ["a", "b"] and not res.evicted
    assert res.improvement_x > 1.0
    for t in res.predicted_before:
        assert res.predicted_after[t] > res.predicted_before[t]
    # the hot slot now carries the smallest fan-in at its level
    fanins = sorted((len(mgr.tree.nodes[n].children)
                     for n in mgr.tree.levels[1]), reverse=True)
    assert fanins == [6, 2]


@pytest.mark.parametrize("slot,hotness", [((1, 0), 2.0), ((1, 1), 2.0),
                                          ((1, 0), 0.6), ((1, 0), 0.3),
                                          ((2, 0), 2.0)])
def test_replan_never_predicted_worse(slot, hotness):
    """Wherever the hot slot is, a congestion replan of a reproducible
    dense canary beside a sparse tenant never predicts less aggregate
    throughput than the tree it leaves."""
    from repro.runtime import CongestionMonitor
    mgr = _mgr()
    mgr.open("canary", mode="dense", num_buckets=8, bucket_elems=1 << 15,
             dtype=jnp.float32, reproducible=True)
    mgr.open("bg", mode="sparse", num_buckets=8, bucket_elems=1 << 15,
             dtype=jnp.float32, k=2048)
    mon = CongestionMonitor(mgr)
    mon.inject(slot, hotness)
    res = mgr.replan(mon, threshold=0.5, hysteresis=0.05)
    assert (sum(res.predicted_after.values())
            >= sum(res.predicted_before.values())), res


def test_replan_hysteresis_blocks_marginal_move():
    """A cheaper tree that doesn't clear the hysteresis margin must not
    move anything (no ping-pong on noise)."""
    mgr = _mgr()
    _open_two(mgr)
    res = mgr.replan(hotness={(1, 0): 2.0}, threshold=0.5,
                     hysteresis=1e9)
    assert not res.replanned and res.reason == "hysteresis"
    assert mgr._epoch == 0 and mgr.active()


def test_replan_accepts_node_id_hotness_and_requires_a_map():
    mgr = _mgr()
    _open_two(mgr)
    hot_switch = mgr.tree.levels[1][0]
    res = mgr.replan(hotness={hot_switch: 2.0})
    assert res.replanned
    with pytest.raises(ValueError, match="monitor= or a hotness="):
        mgr.replan()


def test_congestion_monitor_observe_shapes():
    from repro.runtime import CongestionMonitor
    mgr = _mgr()
    mon = CongestionMonitor(mgr)
    m = mon.observe()
    # idle switch → every physical slot exists at heat 0
    assert set(m.hotness) == {(lvl, i)
                              for lvl, n in mgr.fabric_pools.items()
                              for i in range(n)}
    assert m.peak() == 0.0
    mon.inject((1, 1), 1.5)
    m2 = mon.observe()
    assert m2.hottest() == (1, 1) and m2.of((1, 1)) == 1.5
    with pytest.raises(ValueError, match=">= 0"):
        mon.inject((1, 0), -1.0)


def test_service_scale_slows_measured_and_predicted():
    mgr = _mgr()
    _open_two(mgr)
    base = mgr.schedule()
    slow = mgr.schedule(service_scale=3.0)
    for c in base.counters:
        s = slow.tenant(c.tenant)
        assert s.occupancy_cycles == pytest.approx(3.0
                                                   * c.occupancy_cycles)
        assert s.throughput_pkts < c.throughput_pkts
    pb = {p.tenant: p.bandwidth_pkts for p in mgr.predicted()}
    ps = {p.tenant: p.bandwidth_pkts
          for p in mgr.predicted(service_scale=3.0)}
    assert all(ps[t] < pb[t] for t in pb)


def test_congestion_factor_matches_tree_costs():
    from repro.core import topology
    mgr = _mgr()
    hot = {(1, 0): 2.0}
    assert mgr.congestion_factor({}) == 1.0
    assert mgr.congestion_factor(hot) == pytest.approx(
        topology.tree_cost(mgr.tree, hot, mgr.fabric_pools)
        / topology.tree_cost(mgr.tree, {}, mgr.fabric_pools))
    inf = float("inf")
    all_hot = {(lvl, i): inf for lvl, n in mgr.fabric_pools.items()
               for i in range(n)}
    assert mgr.congestion_factor(all_hot) == inf


# -- hypothesis properties (DESIGN.md §15) ----------------------------------

@given(st.lists(st.tuples(st.integers(0, 2), st.floats(0.0, 5.0)),
                min_size=1, max_size=6),
       st.lists(st.sampled_from(["host_leaf", "leaf_spine"]),
                max_size=3))
@settings(max_examples=40, deadline=None)
def test_hotness_monotone_in_injected_load(injections, flow_links):
    """Adding load — per-slot or per-link-class — never cools any slot."""
    from repro.perfmodel import network_sim as ns
    from repro.runtime import CongestionMonitor
    mgr = _mgr()
    mon = CongestionMonitor(mgr)
    slots = [(lvl, i) for lvl, n in mgr.fabric_pools.items()
             for i in range(n)]
    prev = mon.observe()
    for idx, h in injections:
        mon.inject(slots[idx % len(slots)], h)
        cur = mon.observe()
        assert all(cur.of(s) >= prev.of(s) for s in slots)
        prev = cur
    for link in flow_links:
        mon.inject_flow(ns.BackgroundFlow(link, 25.0))
        cur = mon.observe()
        assert all(cur.of(s) >= prev.of(s) for s in slots)
        prev = cur


@given(st.floats(0.6, 4.0), st.integers(0, 1))
@settings(max_examples=20, deadline=None)
def test_replan_never_oscillates_on_static_load(heat, slot_idx):
    """Under an unchanging congestion map, at most ONE replan happens —
    the argmin tree is a fixed point of the policy."""
    mgr = _mgr()
    _open_two(mgr)
    hot = {(1, slot_idx): heat}
    first = mgr.replan(hotness=hot, threshold=0.5)
    for _ in range(3):
        again = mgr.replan(hotness=hot, threshold=0.5)
        assert not again.replanned, (first.reason, again.reason)
    assert mgr._epoch <= 1


@given(st.integers(1, 3), st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_counters_conserve_across_replan(n_tenants, seed):
    """The PR 5 conservation harness across a replan: on the re-planned
    tree every tenant's shared packet/combine/occupancy counters still
    equal its solo totals — the new interleave reorders the (new) work,
    it never creates or destroys any."""
    from repro.runtime import scheduler as rt_sched
    rng = np.random.default_rng(seed)
    mgr = _mgr()
    for i in range(n_tenants):
        mgr.open(f"t{i}", mode="dense",
                 num_buckets=int(rng.integers(1, 4)),
                 bucket_elems=int(rng.integers(1, 9)) * 512,
                 dtype=jnp.float32)
    res = mgr.replan(hotness={(1, 0): 2.0}, threshold=0.5)
    assert res.replanned or res.reason == "hysteresis"
    shared = mgr.schedule()
    for s in mgr.active():
        solo = rt_sched.simulate_shared(
            [rt_sched.TenantLoad(s.tenant, s.counters,
                                 mgr.params.clusters)]).tenant(s.tenant)
        got = shared.tenant(s.tenant)
        assert got.packets == solo.packets
        assert got.combines == solo.combines
        assert got.occupancy_cycles == pytest.approx(solo.occupancy_cycles)
