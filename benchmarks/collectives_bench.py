"""JAX-side collective microbenchmark.

Three parts:
  * analytic wire bytes per algorithm (the §6.4 switchover on the wire);
  * wall-clock of our shard_map collectives on 8 fake CPU devices,
    executed in a subprocess (the parent process must keep 1 device);
  * the **GradReducer end-to-end benchmark**: the seed per-bucket Python
    dispatch loop (``FlareConfig(arena=False)``) vs the flat-arena
    pipelined hot path (``arena=True``) on the same gradient pytree —
    the headline number of the arena PR, persisted to
    ``BENCH_collectives.json`` at the repo root so the perf trajectory
    is tracked across PRs.
"""
import json
import os
import subprocess
import sys

from repro.core import collectives as coll

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
BENCH_JSON = os.path.join(_ROOT, "BENCH_collectives.json")

_CHILD = r"""
import os, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.core import collectives as coll
from repro.core.engine import FlareConfig, GradReducer


def timeit(fn, *args, iters=5):
    fn(*args)                       # compile + warm
    jax.block_until_ready(fn(*args))
    best = float("inf")             # min over repeats: robust to CI load
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


# --- raw collective wall-clock (seed benchmark, kept) ----------------------
mesh = jax.make_mesh((2, 4), ("pod", "data"),
                     axis_types=(AxisType.Auto,) * 2)
Z = 1 << 22
x = jnp.ones((8, Z), jnp.float32)
with jax.set_mesh(mesh):
    xd = jax.device_put(x, NamedSharding(mesh, P(("pod", "data"), None)))
    for alg in ["ring", "rhd", "fixed_tree", "two_level",
                "psum"]:
        fn = jax.jit(jax.shard_map(
            lambda v, a=alg: coll.allreduce(v[0], ("pod", "data"),
                                            algorithm=a),
            in_specs=(P(("pod", "data"), None),), out_specs=P(None),
            axis_names={"pod", "data"}, check_vma=False))
        dt = timeit(fn, xd, iters=3)
        print(f"collectives.{alg}.Z16MiB.us_per_call,{dt*1e6:.0f},8dev_cpu")

# --- GradReducer end-to-end: seed loop vs arena pipeline -------------------
# the GradReducer's production workload in this repo: the *replicated*
# gradient leaves (norms, biases, routers, gates — FSDP leaves go through
# gather_params' reduce-scatter instead).  ~192 small tensors, ~1.6 MiB,
# 64 KiB reduction blocks → ~26 blocks in flight: the latency-bound
# regime where the paper's B-concurrent-buffers argument (§6.2, §5)
# bites — the seed loop pays 2B(P-1) serialized collective rounds, the
# arena schedule 2(P-1) batched ones.
rng = np.random.default_rng(0)
grads = {}
for i in range(192):
    n = int(rng.integers(256, 4096))
    grads[f"p{i}"] = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
total = sum(int(np.prod(g.shape)) for g in grads.values())
in_specs = {k: P() for k in grads}

mesh8 = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
with jax.set_mesh(mesh8):
    gd = {k: jax.device_put(v, NamedSharding(mesh8, P()))
          for k, v in grads.items()}
    times = {}
    for label, arena, alg in [("legacy_loop", False, "ring"),
                              ("arena_pipeline", True, "ring"),
                              ("legacy_auto", False, "auto"),
                              ("arena_auto", True, "auto")]:
        red = GradReducer(FlareConfig(axes=("data",), algorithm=alg,
                                      bucket_bytes=64 << 10, arena=arena))
        fn = jax.jit(jax.shard_map(
            lambda g, red=red: red(g)[0], in_specs=(in_specs,),
            out_specs=in_specs, axis_names={"data"}, check_vma=False))
        times[label] = timeit(fn, gd, iters=7)
        print(f"gradreducer.{label}.us_per_call,{times[label]*1e6:.0f},"
              f"8dev_cpu_{total*4>>10}KiB_{len(grads)}leaves")
speedup = times["legacy_loop"] / times["arena_pipeline"]
print(f"gradreducer.arena_speedup_x,{speedup:.2f},legacy/arena_ring")
speedup_auto = times["legacy_auto"] / times["arena_auto"]
print(f"gradreducer.arena_speedup_auto_x,{speedup_auto:.2f},legacy/arena_auto")

# --- transport layer: per-bucket scan vs batched arena schedules -----------
# the PR-2 headline: the sparse and int8 transports reduce a whole (B, S)
# dtype arena in one batched schedule (O(log P) / O(1) collectives) vs
# the per-bucket lax.scan ancestor's O(B log P) / O(B); bitwise-equal
# outputs (asserted in multidevice_checks.py group `transports`).
# B=16 8-KiB buckets is the latency-bound many-blocks-in-flight regime
# the arena engine serves (§6.2) — where the batched schedule's
# B-independent collective count bites hardest.
from repro.core import transports

B, S = 16, 1 << 11
arena = jnp.asarray(rng.normal(size=(B, S)).astype(np.float32))
exts = (S,) * B
with jax.set_mesh(mesh8):
    ad = jax.device_put(arena, NamedSharding(mesh8, P()))
    for name, kw in [("sparse", dict(sparse_k_frac=0.01)),
                     ("int8", dict(compression="int8"))]:
        ts = {}
        for mode, batched in [("scan", False), ("batched", True)]:
            cfg = FlareConfig(axes=("data",), **kw)
            t = transports.from_config(cfg, jnp.float32, batched=batched)
            fn = jax.jit(jax.shard_map(
                lambda a, t=t: t(a, jnp.zeros_like(a),
                                 jnp.zeros((B,), jnp.int32), exts)[0],
                in_specs=(P(),), out_specs=P(), axis_names={"data"},
                check_vma=False))
            ts[mode] = timeit(fn, ad, iters=5)
            print(f"transports.{name}.{mode}.us_per_call,"
                  f"{ts[mode]*1e6:.0f},8dev_cpu_B{B}xS{S}")
        print(f"transports.{name}.batched_speedup_x,"
              f"{ts['scan']/ts['batched']:.2f},scan/batched")

# --- flat vs hierarchical transport schedules on a (2, 4) mesh (PR 3) ------
# the tree-driven two-level schedule (DESIGN.md §11): reduce-scatter
# intra-pod, reduce only Z/fanin across pods, all-gather back — vs the
# flat per-axis schedule at full Z on both axes.  Shapes are per
# transport, each in its bandwidth-bound regime where the wire-byte
# model (~(1 + 1/fanin)·Z inter-pod vs 2Z flat) governs: dense 1-MiB
# buckets, int8 256-KiB, sparse 4-MiB with k = 0.05% (the inter-pod hop
# carries coordinate lists instead of dense vectors).
from repro.launch import mesh as launch_mesh

mesh24 = launch_mesh.make_fake_mesh(launch_mesh.FAKE_2D)
HIER_CASES = [
    ("dense", 4, 1 << 18, dict(algorithm="ring"), dict(algorithm="auto")),
    ("int8", 8, 1 << 16, dict(compression="int8"), dict(compression="int8")),
    ("sparse", 8, 1 << 20, dict(sparse_k_frac=0.0005),
     dict(sparse_k_frac=0.0005)),
]
with jax.set_mesh(mesh24):
    for name, b, s, flat_kw, hier_kw in HIER_CASES:
        arena = jnp.asarray(rng.normal(size=(b, s)).astype(np.float32))
        ad = jax.device_put(arena, NamedSharding(mesh24, P()))
        exts = (s,) * b
        ts = {}
        for mode, kw, hier in [("flat", flat_kw, False),
                               ("hier", hier_kw, True)]:
            cfg = FlareConfig(axes=("pod", "data"), hierarchical=hier, **kw)
            t = transports.from_config(cfg, jnp.float32, batched=True)
            fn = jax.jit(jax.shard_map(
                lambda a, t=t, b=b: t(a, jnp.zeros_like(a),
                                      jnp.zeros((b,), jnp.int32),
                                      (a.shape[1],) * b)[0],
                in_specs=(P(),), out_specs=P(), axis_names={"pod", "data"},
                check_vma=False))
            ts[mode] = timeit(fn, ad, iters=5)
            print(f"transports.{name}_{mode}.us_per_call,"
                  f"{ts[mode]*1e6:.0f},2x4dev_cpu_B{b}xS{s}")
        print(f"transports.{name}.hier_speedup_x,"
              f"{ts['flat']/ts['hier']:.2f},flat/hier_2x4mesh")

# --- emulated switch data plane vs flat wire transport (PR 4, PR 7) --------
# FlareConfig(transport="innetwork") reduces the arena through the
# packetized sPIN-handler emulation (repro/switch) instead of the wire
# collectives.  The emulator is a *fidelity* artifact — it pays host-side
# packet framing plus SPMD-masked aggregation on every rank — so the
# tracked number is its overhead factor over the flat wire schedule per
# handler type, not a speedup claim.  ``slotloop`` is the per-slot
# bitwise-oracle schedule (``batched=False``); ``batched_x`` is the
# batched data plane's speedup over it.
B, S = 4, 1 << 14
arena = jnp.asarray(rng.normal(size=(B, S)).astype(np.float32))
exts = (S,) * B
with jax.set_mesh(mesh8):
    ad = jax.device_put(arena, NamedSharding(mesh8, P()))
    for name, kw in [("dense", dict()),
                     ("sparse", dict(sparse_k_frac=0.01)),
                     ("int8", dict(compression="int8"))]:
        ts = {}
        for mode, extra, batched in [
                ("flat", dict(), True),
                ("innetwork", dict(transport="innetwork"), True),
                ("slotloop", dict(transport="innetwork"), False)]:
            cfg = FlareConfig(axes=("data",), **kw, **extra)
            t = transports.from_config(cfg, jnp.float32, batched=batched)
            fn = jax.jit(jax.shard_map(
                lambda a, t=t: t(a, jnp.zeros_like(a),
                                 jnp.zeros((B,), jnp.int32), exts)[0],
                in_specs=(P(),), out_specs=P(), axis_names={"data"},
                check_vma=False))
            ts[mode] = timeit(fn, ad, iters=3)
            print(f"transports.switch.{name}_{mode}.us_per_call,"
                  f"{ts[mode]*1e6:.0f},8dev_cpu_B{B}xS{S}")
        print(f"transports.switch.{name}.overhead_x,"
              f"{ts['innetwork']/ts['flat']:.2f},innetwork/flat")
        print(f"transports.switch.{name}.batched_x,"
              f"{ts['slotloop']/ts['innetwork']:.2f},slotloop/batched")

# --- multi-tenant switch runtime: contention overhead (PR 5) ---------------
# the measured tenant (dense, reproducible fixed-tree) reduces through the
# shared emulated switch with 0/1/3 contending sessions admitted to the
# SessionManager.  Under contention the runtime's adversarial arrival
# interleave perturbs every level's ingress; bitwise the tenant's result
# is UNCHANGED (multidevice group `runtime`), so the tracked number is
# purely the emulator-side cost of modeled contention per tenant count.
from repro.runtime import SessionManager

B, S = 4, 1 << 14
arena = jnp.asarray(rng.normal(size=(B, S)).astype(np.float32))
exts = (S,) * B
with jax.set_mesh(mesh8):
    ad = jax.device_put(arena, NamedSharding(mesh8, P()))
    fns = {}
    for nten in (1, 2, 4):
        mgr = SessionManager(("data",), (8,), seed=0)
        for i in range(1, nten):
            mgr.open(f"bg{i}", mode=("sparse", "int8", "dense")[i % 3],
                     num_buckets=B, bucket_elems=S, dtype=jnp.float32,
                     k=256)
        cfg = FlareConfig(axes=("data",), transport="innetwork",
                          reproducible=True)
        t = transports.from_config(cfg, jnp.float32, manager=mgr,
                                   tenant="t0")
        fns[nten] = jax.jit(jax.shard_map(
            lambda a, t=t: t(a, None, jnp.zeros((B,), jnp.int32), exts)[0],
            in_specs=(P(),), out_specs=P(), axis_names={"data"},
            check_vma=False))
        jax.block_until_ready(fns[nten](ad))   # compile + warm all first
    # interleaved measurement rounds: machine noise hits every tenant
    # count alike instead of whichever variant runs first
    ts = {n: float("inf") for n in fns}
    for _round in range(6):
        for n, fn in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn(ad))
            ts[n] = min(ts[n], time.perf_counter() - t0)
    for nten in (1, 2, 4):
        print(f"transports.runtime.tenants{nten}.us_per_call,"
              f"{ts[nten]*1e6:.0f},8dev_cpu_B{B}xS{S}_dense_tenant")
    print(f"transports.runtime.contention_x,"
          f"{ts[4]/ts[1]:.2f},tenants4/tenants1")

# --- lossy-fabric reliability layer (PR 6) ---------------------------------
# dense in-network with no plan / armed-but-fault-free plan / surviving
# 1% drop plan.  The tracked number is the fault-free overhead factor of
# the checksum + seen-bitmap + NACK-retransmit machinery over the PR 5
# switch baseline (acceptance: < 1.2x), plus the lossy run's wall clock
# with deterministic in-switch retries and the plan's static retry rate.
from repro.switch import dataplane as sw_dp
from repro.switch.packets import FaultPlan
B, S = 4, 1 << 14
arena = jnp.asarray(rng.normal(size=(B, S)).astype(np.float32))
exts = (S,) * B
with jax.set_mesh(mesh8):
    ad = jax.device_put(arena, NamedSharding(mesh8, P()))
    ts = {}
    for name, plan in [("baseline", None),
                       ("reliable", FaultPlan()),
                       ("lossy", FaultPlan(seed=1, drop=0.01))]:
        cfg = FlareConfig(axes=("data",), transport="innetwork",
                          fault_plan=plan)
        t = transports.from_config(cfg, jnp.float32, batched=True)
        fn = jax.jit(jax.shard_map(
            lambda a, t=t: t(a, jnp.zeros_like(a),
                             jnp.zeros((B,), jnp.int32), exts)[0],
            in_specs=(P(),), out_specs=P(), axis_names={"data"},
            check_vma=False))
        ts[name] = timeit(fn, ad, iters=3)
        print(f"transports.chaos.{name}.us_per_call,"
              f"{ts[name]*1e6:.0f},8dev_cpu_B{B}xS{S}")
    print(f"transports.chaos.overhead_x,"
          f"{ts['reliable']/ts['baseline']:.2f},reliable/baseline_fault_free")
    counts = sw_dp.level_packet_counts([8], B, S, jnp.float32, mode="dense")
    sched = sw_dp.fault_schedules(FaultPlan(seed=1, drop=0.01), counts)[0]
    print(f"transports.chaos.retry_rate,"
          f"{sched.retransmits/counts[0][1]:.4f},"
          f"retrans{sched.retransmits}_of_{counts[0][1]}pkts_drop1pct")

# --- congestion-aware dynamic trees (PR 8, DESIGN.md §15) ------------------
# a hot leaf slot on the two-level fabric triggers SessionManager.replan
# onto the cheapest tree under the congestion map.  Tracked: the
# predicted aggregate throughput on the static tree (congested) vs the
# dynamically re-planned tree, and their ratio — the replan's predicted
# win.  Control-plane only (counters + analytic model, no tensors); the
# hysteresis contract guarantees the ratio is > 1.0 whenever a replan
# happens at all.
from repro.runtime import CongestionMonitor
cmgr = SessionManager(("pod", "data"), (2, 4), max_sessions=4)
cmgr.open("canary", mode="dense", num_buckets=8, bucket_elems=1 << 15,
          dtype=jnp.float32, reproducible=True)
cmgr.open("bg", mode="sparse", num_buckets=8, bucket_elems=1 << 15,
          dtype=jnp.float32, k=2048)
cmon = CongestionMonitor(cmgr)
cmon.inject((1, 0), 2.0)
cres = cmgr.replan(cmon, threshold=0.5, hysteresis=0.05)
c_static = sum(cres.predicted_before.values())
c_dynamic = sum(cres.predicted_after.values())
print(f"transports.canary.static.pred_pkts_per_cy,{c_static:.4f},"
      f"hot_leaf_h2.0_2x4fabric")
print(f"transports.canary.dynamic.pred_pkts_per_cy,{c_dynamic:.4f},"
      f"replanned={cres.replanned}")
print(f"transports.canary.contention_x,{c_dynamic/c_static:.2f},"
      f"dynamic/static_pred")
"""

# tiny-shape variant for `run.py --quick` / the tier-1 smoke test: all
# three transports, scan vs batched, seconds not minutes — the harness
# can't silently rot if CI exercises this end to end.
_QUICK_CHILD = r"""
import os, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.core import transports
from repro.core.engine import FlareConfig


def timeit(fn, *args, iters=2):
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


B, S = 4, 2048
rng = np.random.default_rng(0)
arena = jnp.asarray(rng.normal(size=(B, S)).astype(np.float32))
exts = (S,) * B
mesh8 = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
with jax.set_mesh(mesh8):
    ad = jax.device_put(arena, NamedSharding(mesh8, P()))
    for name, kw in [("dense", dict(algorithm="ring")),
                     ("sparse", dict(sparse_k_frac=0.01)),
                     ("int8", dict(compression="int8"))]:
        ts = {}
        for mode, batched in [("scan", False), ("batched", True)]:
            cfg = FlareConfig(axes=("data",), **kw)
            t = transports.from_config(cfg, jnp.float32, batched=batched)
            fn = jax.jit(jax.shard_map(
                lambda a, t=t: t(a, jnp.zeros_like(a),
                                 jnp.zeros((B,), jnp.int32), exts)[0],
                in_specs=(P(),), out_specs=P(), axis_names={"data"},
                check_vma=False))
            ts[mode] = timeit(fn, ad)
            print(f"quick.{name}.{mode}.us_per_call,{ts[mode]*1e6:.0f},"
                  f"8dev_cpu_B{B}xS{S}")
        print(f"quick.{name}.batched_speedup_x,"
              f"{ts['scan']/ts['batched']:.2f},scan/batched")

# flat vs hierarchical, tiny shapes, (2, 4) mesh — keeps the tree-driven
# schedule plumbing (PR 3) under the tier-1 smoke test
if os.environ.get("REPRO_QUICK_INJECT_FAIL"):
    raise RuntimeError("injected failure (REPRO_QUICK_INJECT_FAIL)")
from repro.launch import mesh as launch_mesh
mesh24 = launch_mesh.make_fake_mesh(launch_mesh.FAKE_2D)
with jax.set_mesh(mesh24):
    ad = jax.device_put(arena, NamedSharding(mesh24, P()))
    for name, kw in [("dense", dict()),
                     ("sparse", dict(sparse_k_frac=0.01)),
                     ("int8", dict(compression="int8"))]:
        ts = {}
        for mode, hier in [("flat", False), ("hier", True)]:
            cfg = FlareConfig(axes=("pod", "data"), hierarchical=hier, **kw)
            t = transports.from_config(cfg, jnp.float32, batched=True)
            fn = jax.jit(jax.shard_map(
                lambda a, t=t: t(a, jnp.zeros_like(a),
                                 jnp.zeros((B,), jnp.int32), exts)[0],
                in_specs=(P(),), out_specs=P(), axis_names={"pod", "data"},
                check_vma=False))
            ts[mode] = timeit(fn, ad)
            print(f"quick.hier.{name}.{mode}.us_per_call,{ts[mode]*1e6:.0f},"
                  f"2x4dev_cpu_B{B}xS{S}")
        print(f"quick.hier.{name}.speedup_x,"
              f"{ts['flat']/ts['hier']:.2f},flat/hier_2x4mesh")

# emulated switch data plane vs flat wire transport (PR 4, PR 7), tiny
# shapes — keeps FlareConfig(transport="innetwork") + the repro/switch
# packet/handler plumbing under the tier-1 smoke gate for every handler
# type, in both the batched plane and the slot-loop oracle schedule
with jax.set_mesh(mesh8):
    ad = jax.device_put(arena, NamedSharding(mesh8, P()))
    for name, kw in [("dense", dict()),
                     ("sparse", dict(sparse_k_frac=0.01)),
                     ("int8", dict(compression="int8"))]:
        ts = {}
        for mode, extra, batched in [
                ("flat", dict(), True),
                ("innetwork", dict(transport="innetwork"), True),
                ("slotloop", dict(transport="innetwork"), False)]:
            cfg = FlareConfig(axes=("data",), **kw, **extra)
            t = transports.from_config(cfg, jnp.float32, batched=batched)
            fn = jax.jit(jax.shard_map(
                lambda a, t=t: t(a, jnp.zeros_like(a),
                                 jnp.zeros((B,), jnp.int32), exts)[0],
                in_specs=(P(),), out_specs=P(), axis_names={"data"},
                check_vma=False))
            ts[mode] = timeit(fn, ad)
            print(f"quick.switch.{name}.{mode}.us_per_call,"
                  f"{ts[mode]*1e6:.0f},8dev_cpu_B{B}xS{S}")
        print(f"quick.switch.{name}.overhead_x,"
              f"{ts['innetwork']/ts['flat']:.2f},innetwork/flat")
        print(f"quick.switch.{name}.batched_x,"
              f"{ts['slotloop']/ts['innetwork']:.2f},slotloop/batched")

# multi-tenant switch runtime (PR 5): the measured tenant reduces through
# the shared emulated switch while 0/1/3 contending sessions are admitted
# — tenants1 is the idle-switch baseline (no arrival perturbation), the
# contention rows pay the runtime's adversarial interleave.  Keeps the
# SessionManager → transports → dataplane plumbing under the tier-1
# smoke gate.
from repro.runtime import SessionManager
with jax.set_mesh(mesh8):
    ad = jax.device_put(arena, NamedSharding(mesh8, P()))
    ts = {}
    for nten in (1, 2, 4):
        mgr = SessionManager(("data",), (8,), seed=0)
        for i in range(1, nten):
            mgr.open(f"bg{i}", mode=("sparse", "int8", "dense")[i % 3],
                     num_buckets=B, bucket_elems=S, dtype=jnp.float32,
                     k=64)
        cfg = FlareConfig(axes=("data",), transport="innetwork",
                          reproducible=True)
        t = transports.from_config(cfg, jnp.float32, manager=mgr,
                                   tenant="t0")
        fn = jax.jit(jax.shard_map(
            lambda a, t=t: t(a, None, jnp.zeros((B,), jnp.int32),
                             exts)[0],
            in_specs=(P(),), out_specs=P(), axis_names={"data"},
            check_vma=False))
        ts[nten] = timeit(fn, ad)
        print(f"quick.runtime.tenants{nten}.us_per_call,"
              f"{ts[nten]*1e6:.0f},8dev_cpu_B{B}xS{S}_dense_tenant")
    print(f"quick.runtime.contention_x,{ts[4]/ts[1]:.2f},tenants4/tenants1")

# lossy-fabric reliability layer (PR 6, DESIGN.md §14): dense in-network
# with (a) no fault plan — the PR 5 baseline; (b) an armed all-zero
# FaultPlan — checksum verify + seen-bitmap admission + retransmit
# machinery active but fault-free (the tracked overhead factor); (c) a
# surviving 1% drop plan — NACK-driven retries resolve in-switch and the
# result stays bitwise (multidevice group `chaos`).  retry_rate is read
# off the plan's deterministic static schedule — the same counters the
# traced plane accumulates (they are asserted equal in tests).
from repro.switch import dataplane as sw_dp
from repro.switch.packets import FaultPlan
with jax.set_mesh(mesh8):
    ad = jax.device_put(arena, NamedSharding(mesh8, P()))
    ts = {}
    for name, plan in [("baseline", None),
                       ("reliable", FaultPlan()),
                       ("lossy", FaultPlan(seed=1, drop=0.01))]:
        cfg = FlareConfig(axes=("data",), transport="innetwork",
                          fault_plan=plan)
        t = transports.from_config(cfg, jnp.float32, batched=True)
        fn = jax.jit(jax.shard_map(
            lambda a, t=t: t(a, jnp.zeros_like(a),
                             jnp.zeros((B,), jnp.int32), exts)[0],
            in_specs=(P(),), out_specs=P(), axis_names={"data"},
            check_vma=False))
        ts[name] = timeit(fn, ad)
        print(f"quick.chaos.{name}.us_per_call,{ts[name]*1e6:.0f},"
              f"8dev_cpu_B{B}xS{S}")
    print(f"quick.chaos.overhead_x,{ts['reliable']/ts['baseline']:.2f},"
          f"reliable/baseline_fault_free")
    counts = sw_dp.level_packet_counts([8], B, S, jnp.float32, mode="dense")
    sched = sw_dp.fault_schedules(FaultPlan(seed=1, drop=0.01), counts)[0]
    print(f"quick.chaos.retry_rate,{sched.retransmits/counts[0][1]:.4f},"
          f"retrans{sched.retransmits}_of_{counts[0][1]}pkts_drop1pct")

# congestion-aware dynamic trees (PR 8, DESIGN.md §15): a hot leaf slot
# on the two-level fabric triggers SessionManager.replan onto the
# cheapest tree under the congestion map.  Tracked: predicted aggregate
# throughput on the static (congested) tree vs the re-planned one, and
# their ratio — run_quick() fails if a replan ever *degrades* the
# prediction (the hysteresis contract).  Control-plane only.
from repro.runtime import CongestionMonitor
cmgr = SessionManager(("pod", "data"), (2, 4), max_sessions=4)
cmgr.open("canary", mode="dense", num_buckets=8, bucket_elems=1 << 15,
          dtype=jnp.float32, reproducible=True)
cmgr.open("bg", mode="sparse", num_buckets=8, bucket_elems=1 << 15,
          dtype=jnp.float32, k=2048)
cmon = CongestionMonitor(cmgr)
cmon.inject((1, 0), 2.0)
cres = cmgr.replan(cmon, threshold=0.5, hysteresis=0.05)
c_static = sum(cres.predicted_before.values())
c_dynamic = sum(cres.predicted_after.values())
print(f"quick.canary.static.pred_pkts_per_cy,{c_static:.4f},"
      f"hot_leaf_h2.0_2x4fabric")
print(f"quick.canary.dynamic.pred_pkts_per_cy,{c_dynamic:.4f},"
      f"replanned={cres.replanned}")
print(f"quick.canary.contention_x,{c_dynamic/c_static:.2f},"
      f"dynamic/static_pred")

# flight recorder (PR 9, DESIGN.md §16): telemetry is an off-path
# observer — counters come from the static schedules at trace/admission
# time and spans wrap *tracing*, never the compiled program — so the
# instrumented dense in-network step must cost the same as the bare one
# (run_quick() gates the ratio at <= 1.05x).  Interleaved measurement
# rounds, like the runtime section: noise hits both variants alike.
from repro.obs import HealthMonitor, Telemetry, counting_clock, timeline
obs_tm = Telemetry.create()
# the §17 health plane rides the gate: the telemetry variant runs
# WITH a HealthMonitor attached and polling each round, so any traced
# op the monitor smuggled into the step would blow the ratio.  The
# poll itself is host-side registry reads, priced separately below
# (quick.health.poll.us_per_call) — it stays outside the timed window
# so the gate keeps measuring the step, not the detector sweep
obs_hm = HealthMonitor(obs_tm)
with jax.set_mesh(mesh8):
    ad = jax.device_put(arena, NamedSharding(mesh8, P()))
    fns = {}
    for label, tm in [("bare", None), ("telemetry", obs_tm)]:
        cfg = FlareConfig(axes=("data",), transport="innetwork",
                          telemetry=tm)
        t = transports.from_config(cfg, jnp.float32, batched=True)
        fns[label] = jax.jit(jax.shard_map(
            lambda a, t=t: t(a, jnp.zeros_like(a),
                             jnp.zeros((B,), jnp.int32), exts)[0],
            in_specs=(P(),), out_specs=P(), axis_names={"data"},
            check_vma=False))
        jax.block_until_ready(fns[label](ad))   # compile + warm both
    ts = {label: float("inf") for label in fns}
    # the gate is a tight ratio (1.05x) on a noisy shared CPU, where the
    # two mins can land in different load bursts: each round times both
    # variants back to back, in alternating order, and the gate reads
    # the median of the per-round ratios
    ratios = []
    for rnd in range(100):
        dt = {}
        for label in (("bare", "telemetry") if rnd % 2 == 0
                      else ("telemetry", "bare")):
            t0 = time.perf_counter()
            jax.block_until_ready(fns[label](ad))
            dt[label] = time.perf_counter() - t0
            ts[label] = min(ts[label], dt[label])
            if label == "telemetry":
                obs_hm.poll()
        ratios.append(dt["telemetry"] / dt["bare"])
    for label in ("bare", "telemetry"):
        print(f"quick.obs.{label}.us_per_call,{ts[label]*1e6:.0f},"
              f"8dev_cpu_B{B}xS{S}_dense_innetwork")
    print(f"quick.obs.overhead_x,{np.median(ratios):.2f},"
          f"telemetry/bare_dense_innetwork_paired_median")
    t0 = time.perf_counter()
    for _ in range(100):
        obs_hm.poll()
    print(f"quick.health.poll.us_per_call,"
          f"{(time.perf_counter()-t0)/100*1e6:.1f},"
          f"4detectors_hostside_registry")

# trace-export round trip: a 2-tenant manager run under a counting
# clock, modeled timeline laid in, exported to Chrome JSON and loaded
# back — the row value is the track count, and the child asserts every
# tenant owns at least one track (the Perfetto smoke of satellite f).
import json as _json, tempfile
tm2 = Telemetry.create(clock=counting_clock())
mgr2 = SessionManager(("data",), (8,), seed=0, telemetry=tm2)
with jax.set_mesh(mesh8):
    ad = jax.device_put(arena, NamedSharding(mesh8, P()))
    for tenant, kw in [("a", dict()), ("b", dict(compression="int8"))]:
        cfg = FlareConfig(axes=("data",), transport="innetwork",
                          telemetry=tm2, **kw)
        t = transports.from_config(cfg, jnp.float32, manager=mgr2,
                                   tenant=tenant)
        fn = jax.jit(jax.shard_map(
            lambda a, t=t: t(a, jnp.zeros_like(a),
                             jnp.zeros((B,), jnp.int32), exts)[0],
            in_specs=(P(),), out_specs=P(), axis_names={"data"},
            check_vma=False))
        jax.block_until_ready(fn(ad))
timeline.manager_tracks(tm2.tracer, mgr2)
trace_path = os.path.join(tempfile.mkdtemp(), "quick_trace.json")
tm2.export_trace(trace_path)
with open(trace_path) as f:
    doc = _json.load(f)                         # must be valid JSON
tracks = sorted({ev["args"]["name"] for ev in doc["traceEvents"]
                 if ev.get("ph") == "M" and ev["name"] == "thread_name"})
for tenant in ("a", "b"):
    owned = [tr for tr in tracks if tenant in tr.split("/")]
    assert owned, f"tenant {tenant} owns no trace track: {tracks}"
assert doc.get("metrics"), "exported trace carries no metrics snapshot"
print(f"quick.obs.trace.tracks,{len(tracks)},tenants2_chrome_json")
"""


def run(write_json: bool = True):
    rows = []
    z = 16 << 20
    for alg in ["ring", "rhd", "fixed_tree", "two_level",
                "psum"]:
        wb = coll.wire_bytes_per_rank(z, 16, 2, algorithm=alg)
        rows.append((f"collectives.{alg}.wire_bytes_per_rank.Z16MiB",
                     int(wb), f"ratio_to_Z={wb/z:.2f}"))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_ROOT, "src"), env.get("PYTHONPATH", "")])
    ok = False
    try:
        out = subprocess.run([sys.executable, "-c", _CHILD],
                             capture_output=True, text=True, timeout=900,
                             env=env)
        if out.returncode != 0:                         # pragma: no cover
            raise RuntimeError(out.stderr[-2000:])
        for line in out.stdout.splitlines():
            if line.startswith(("collectives.", "gradreducer.",
                                "transports.")):
                name, val, der = line.split(",")
                rows.append((name, float(val), der))
        ok = True
    except Exception as e:                              # pragma: no cover
        rows.append(("collectives.wallclock.error", 0, repr(e)))
    if write_json and ok:
        # only persist complete runs: a failed child must not overwrite
        # the tracked perf trajectory with a wall-clock-less record
        write_bench_json(rows)
    return rows


#: Every row ``--quick`` must produce; a child that dies (or silently
#: stops printing) after a partial run is a harness failure, not a
#: shorter report.
QUICK_EXPECTED_ROWS = frozenset(
    [f"quick.{t}.{m}.us_per_call" for t in ("dense", "sparse", "int8")
     for m in ("scan", "batched")]
    + [f"quick.{t}.batched_speedup_x" for t in ("dense", "sparse", "int8")]
    + [f"quick.hier.{t}.{m}.us_per_call"
       for t in ("dense", "sparse", "int8") for m in ("flat", "hier")]
    + [f"quick.hier.{t}.speedup_x" for t in ("dense", "sparse", "int8")]
    + [f"quick.switch.{t}.{m}.us_per_call"
       for t in ("dense", "sparse", "int8")
       for m in ("flat", "innetwork", "slotloop")]
    + [f"quick.switch.{t}.overhead_x" for t in ("dense", "sparse", "int8")]
    + [f"quick.switch.{t}.batched_x" for t in ("dense", "sparse", "int8")]
    + [f"quick.runtime.tenants{n}.us_per_call" for n in (1, 2, 4)]
    + ["quick.runtime.contention_x"]
    + [f"quick.chaos.{n}.us_per_call"
       for n in ("baseline", "reliable", "lossy")]
    + ["quick.chaos.overhead_x", "quick.chaos.retry_rate"]
    + [f"quick.canary.{m}.pred_pkts_per_cy" for m in ("static", "dynamic")]
    + ["quick.canary.contention_x"]
    + [f"quick.obs.{m}.us_per_call" for m in ("bare", "telemetry")]
    + ["quick.obs.overhead_x", "quick.obs.trace.tracks",
       "quick.health.poll.us_per_call"])


def run_quick():
    """Tiny-shape transport smoke benchmark (never touches the JSON).

    Exercises all three transports — scan vs batched on the flat mesh,
    flat vs hierarchical on the (2, 4) mesh — on 8 fake CPU devices in
    seconds; the tier-1 smoke test (``tests/test_benchmarks.py``) runs
    this so the benchmark harness can't silently rot between full
    ``--json`` refreshes.  Raises (→ ``benchmarks/run.py --quick`` exits
    nonzero) if the child fails OR comes back with an incomplete row
    set — a crashed benchmark must never look like a passing run with
    fewer rows.
    """
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_ROOT, "src"), env.get("PYTHONPATH", "")])
    out = subprocess.run([sys.executable, "-c", _QUICK_CHILD],
                         capture_output=True, text=True, timeout=600,
                         env=env)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-2000:])
    rows = []
    for line in out.stdout.splitlines():
        if line.startswith("quick."):
            name, val, der = line.split(",")
            rows.append((name, float(val), der))
    missing = QUICK_EXPECTED_ROWS - {name for name, _, _ in rows}
    if missing:
        raise RuntimeError(
            f"--quick benchmark incomplete; missing rows: {sorted(missing)}")
    for name, val, _der in rows:
        # the hysteresis contract: a congestion replan may decline to
        # move, but it must never land on a tree with a *worse*
        # predicted aggregate throughput
        if name == "quick.canary.contention_x" and val < 1.0:
            raise RuntimeError(
                f"congestion replan degraded predicted throughput "
                f"({val:.2f}x dynamic/static)")
        # the §16 overhead contract: telemetry never touches the traced
        # program, so the instrumented step may not cost more than noise
        if name == "quick.obs.overhead_x" and val > 1.05:
            raise RuntimeError(
                f"telemetry overhead on the dense in-network step is "
                f"{val:.2f}x (contract: <= 1.05x)")
        # every tenant must own at least one exported trace track; the
        # child already asserts per-tenant ownership — this gates the
        # aggregate count surviving the round trip
        if name == "quick.obs.trace.tracks" and val < 2:
            raise RuntimeError(
                f"trace export round-trip lost tenant tracks ({val:.0f})")
    return rows


def check_regressions(fresh_rows, path: str | None = None, *,
                      limit: float = 0.20) -> list[str]:
    """Perf-regression sentinel: fresh ratio rows vs the committed JSON.

    Compares every derived ratio row (``*_x``: ``overhead_x``,
    ``contention_x``, ``speedup_x``, ``batched_x``, ...) of
    ``fresh_rows`` against the tracked ``BENCH_collectives.json``
    baseline and returns one failure string per row degraded by more
    than ``limit`` (default 20%).  Direction-aware: ``overhead_x`` rows
    are lower-is-better, every other ratio is higher-is-better.
    Absolute ``us_per_call`` rows are *not* gated — wall-clock noise
    across machines would make the sentinel cry wolf; the ratios are
    machine-relative by construction.  The baseline's provenance
    ``meta`` (PR 9) is quoted in each failure so a trip is auditable
    against the commit that set the bar.
    """
    with open(BENCH_JSON if path is None else path) as f:
        baseline = json.load(f)
    meta = baseline.get("meta", {})
    provenance = (f"baseline {meta.get('git_sha', 'unknown')[:12]} "
                  f"@ {meta.get('timestamp_utc', 'unknown')}")
    failures = []
    for name, val, _der in fresh_rows:
        if not name.split(".")[-1].endswith("_x"):
            continue
        rec = baseline.get(name)
        if rec is None:                 # new row: nothing to regress from
            continue
        base = float(rec["value"] if isinstance(rec, dict) else rec)
        if base <= 0.0:
            continue
        if name.endswith("overhead_x"):
            degraded = val > base * (1.0 + limit)
            arrow = f"{base:.2f} -> {val:.2f} (lower is better)"
        else:
            degraded = val < base * (1.0 - limit)
            arrow = f"{base:.2f} -> {val:.2f} (higher is better)"
        if degraded:
            failures.append(f"{name}: {arrow}, past the {limit:.0%} "
                            f"limit [{provenance}]")
    return failures


def bench_meta() -> dict:
    """Provenance stamped under the ``meta`` key of the tracked JSON.

    A perf trajectory without its generation context is unauditable: the
    git sha ties a number to the code that produced it, the mesh shapes
    and jax version to the execution substrate, the UTC timestamp to the
    refresh cadence.  Git being absent (tarball checkout) degrades to
    ``"unknown"`` rather than failing the run.
    """
    import datetime

    import jax
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=_ROOT, capture_output=True,
            text=True, timeout=10).stdout.strip() or "unknown"
    except Exception:                               # pragma: no cover
        sha = "unknown"
    return {
        "git_sha": sha,
        "mesh_shapes": ["8", "2x4"],
        "jax_version": jax.__version__,
        "timestamp_utc": datetime.datetime.now(datetime.timezone.utc)
                                 .strftime("%Y-%m-%dT%H:%M:%SZ"),
    }


def write_bench_json(rows, path: str = BENCH_JSON, meta: dict | None = None,
                     ) -> None:
    """Persist the wall-clock rows (the tracked perf trajectory)."""
    record = {name: {"value": val, "derived": der}
              for name, val, der in rows}
    record["meta"] = bench_meta() if meta is None else meta
    with open(path, "w") as f:
        json.dump(record, f, indent=1, sort_keys=True)
        f.write("\n")


if __name__ == "__main__":
    for r in run():
        print(",".join(map(str, r)))
