"""Flare: flexible in-network allreduce, adapted to JAX/TPU meshes.

Public API:
  - ``collectives``: ring / rhd / fixed-tree / two-level / psum allreduce
    primitives (call inside a manual ``shard_map`` region).
  - ``sparse``: the §7 top-k sparse allreduce with densify-on-overflow.
  - ``compression``: int8 transport + error feedback (F1).
  - ``transports``: the unified transport layer — dense / int8 / sparse
    batched (B, S) arena schedules behind one dispatch.
  - ``reproducible``: bitwise-deterministic reduction (F3).
  - ``fsdp``: parameter gather / gradient reduce-scatter custom_vjp.
  - ``engine.FlareConfig`` / ``engine.GradReducer``: the composable
    gradient-reduction engine used by the training loop.
  - ``topology``: reduction trees + the control-plane network manager.
"""
from repro.core import (collectives, compression, fsdp, reproducible,
                        sparse, topology, transports)
from repro.core.engine import FlareConfig, GradReducer

__all__ = [
    "collectives", "compression", "fsdp", "reproducible", "sparse",
    "topology", "transports", "FlareConfig", "GradReducer",
]
