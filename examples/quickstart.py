"""Quickstart: the Flare collective family on 8 (fake) devices.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.core import collectives as coll, compression, reproducible, sparse

mesh = jax.make_mesh((2, 4), ("pod", "data"),
                     axis_types=(AxisType.Auto,) * 2)
Z = 1 << 16
rng = np.random.default_rng(0)
contrib = jnp.asarray(rng.normal(size=(8, Z)).astype(np.float32))
oracle = np.asarray(contrib).sum(0)


def run(fn):
    g = jax.jit(jax.shard_map(fn, in_specs=(P(("pod", "data"), None),),
                              out_specs=P(None),
                              axis_names={"pod", "data"},
                              check_vma=False))
    with jax.set_mesh(mesh):
        x = jax.device_put(contrib,
                           NamedSharding(mesh, P(("pod", "data"), None)))
        return np.asarray(g(x))


print(f"allreduce of {Z} floats across a 2-pod x 4-chip mesh\n")
for alg in ["ring", "rhd", "fixed_tree",
            "two_level", "psum", "auto"]:
    out = run(lambda x, a=alg: coll.allreduce(x[0], ("pod", "data"),
                                              algorithm=a))
    wire = coll.wire_bytes_per_rank(Z * 4, 4, 2, algorithm=alg
                                    if alg not in ("auto", "psum")
                                    else "ring")
    print(f"  {alg:12s} max_err={np.abs(out - oracle).max():.2e} "
          f"wire/rank={wire/2**10:.0f} KiB")

print("\nreproducible (F3): bitwise-stable fixed-tree reduction")
a = run(lambda x: reproducible.reproducible_allreduce(x[0], ("pod", "data")))
b = run(lambda x: reproducible.reproducible_allreduce(x[0], ("pod", "data")))
print(f"  run1 == run2 bitwise: {a.tobytes() == b.tobytes()}")

print("\nsparse §7: top-1% with densify-on-overflow")
out = run(lambda x: sparse.sparse_allreduce(x[0], "data", k=Z // 100)[0])
print(f"  nnz(result) = {(out != 0).sum()} of {Z}")

print("\nint8 transport (F1) with fp32 accumulation")
out = run(lambda x: coll.allreduce_rhd(
    compression.quantized_allreduce(x[0], "data"), "pod"))
print(f"  rel_err = {np.abs(out - oracle).max() / np.abs(oracle).max():.4f} "
      f"(wire = 1/4 of fp32)")

print("\nflight recorder (DESIGN.md §16): counters without touching the trace")
from repro.obs import Telemetry
from repro.switch import dataplane

tm = Telemetry.create()
tm.record_switch_counters(
    "demo", dataplane.plan_counters(("pod", "data"), (2, 4), 4, Z // 4,
                                    jnp.float32))
pkts = tm.registry.value("switch.demo.l1.ingress_packets")
print(f"  switch.demo.l1.ingress_packets = {pkts:.0f} "
      f"(static plan counters; full runs: "
      f"launch/train.py --trace-out/--metrics-out "
      f"+ python -m repro.obs.report)")

print("\nhealth plane (DESIGN.md §17): detectors over the recorder")
from repro.obs import HealthMonitor, counting_clock

tm.registry.gauge("congestion.l1s0.hotness").set(0.8)   # a hot leaf slot
hm = HealthMonitor(tm, clock=counting_clock())
for inc in hm.poll():
    print(f"  [{inc.severity}] {inc.detector}: {inc.summary} "
          f"(action: {inc.action})")
print(f"  (full runs: launch/train.py --tenants 2 --health-policy auto "
      f"--incidents-out inc.json + python -m repro.obs.report "
      f"--incidents inc.json --fail-on critical)")
