"""The Flare gradient-reduction engine (the paper's technique, first-class).

``GradReducer`` is the composable entry point that training loops call on
an *unreduced* gradient pytree inside a manual ``shard_map`` region.  It:

  1. packs leaves into reduction blocks — by default through the
     **flat-arena plan** (``core/arena.py``): one padded buffer per
     dtype, equal-size buckets as a leading axis, per-leaf offsets
     computed once per pytree structure,
  2. per dtype group, selects a **transport** (``core/transports.py``):
     dense lossless (with the paper's §6.4 size switchover — tree <
     128 KiB ≤ rhd < 512 KiB ≤ ring/two-level), int8 quantized (F1), or
     §7 top-k sparse — the three-way dispatch lives in exactly one place,
  3. reduces **all B buckets of a group in one batched schedule**: the
     dense path vmaps its collective rounds, the sparse path issues one
     ppermute per recursive-doubling step carrying every bucket's
     coordinate list, the int8 path moves the whole arena's payload in a
     single all_to_all/all_gather pair — the paper's multi-buffer
     aggregation (§6.2) applied to every transport, not just dense,
  4. folds top-k + error feedback into the same trace, with the EF
     residual computed by ``compression.error_feedback_step`` and ``k``
     derived from each bucket's unpadded extent (``sparse.sparse_k``),
  5. staggers concurrent blocks' ring phases (staggered sending, §5) via
     a per-bucket phase scalar,
  6. guarantees bitwise reproducibility when asked (F3: fixed-tree only,
     fp32 accumulation) — the arena and legacy paths are bitwise-equal
     there because the fixed tree combines elementwise.

``FlareConfig(arena=False)`` keeps the per-bucket loop alive as the
benchmark baseline (``benchmarks/collectives_bench.py`` measures both);
it routes through the same transport objects as a loop over B=1 groups,
so the wire math is shared and only the batching differs.

Error-feedback state is functional: ``reduce(grads, state) -> (out,
state)``; the trainer threads it through its optimizer state.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro import compat
from repro.core import arena as arena_mod
from repro.core import bucketing, transports


@dataclasses.dataclass(frozen=True)
class FlareConfig:
    """Configuration of the in-network-style gradient reduction."""

    axes: tuple[str, ...] = ("data",)   # (outer..., inner); inner = leaf level
    algorithm: str = "auto"             # auto|ring|rhd|fixed_tree|
    #                                     two_level|hierarchical|psum
    reproducible: bool = False          # F3: bitwise-deterministic reduction
    compression: str = "none"           # none|int8  (F1 transport dtypes)
    sparse_k_frac: float = 0.0          # >0 → §7 sparse allreduce
    density_threshold: float = 0.25     # sparse densify-on-overflow point
    bucket_bytes: int = 4 << 20
    stagger: bool = True                # §5 staggered sending
    mean: bool = False                  # divide by world size after reduce
    arena: bool = True                  # flat-arena pipelined hot path
    #: flat vs hierarchical (tree-driven) wire schedule on multi-axis
    #: meshes.  None → the reduction tree decides from the mesh shape
    #: (``topology.transport_schedule``); True/False force it.
    hierarchical: bool | None = None
    #: ``"auto"`` — the wire transports (host-side collectives);
    #: ``"innetwork"`` — the emulated sPIN switch data plane
    #: (``repro.switch``): arenas reduce leaf → switch → leaf on the
    #: mesh tree with packet handlers (dense / int8 / sparse picked by
    #: the same compression/sparse_k_frac fields).
    transport: str = "auto"
    #: deterministic lossy-fabric injection for the in-network transport
    #: (``switch.packets.FaultPlan``, DESIGN.md §14).  The reliability
    #: layer recovers surviving plans bitwise; a plan the retry budget
    #: cannot recover degrades the session to the wire transport.
    fault_plan: Any = None
    #: ``repro.obs.Telemetry`` flight recorder (DESIGN.md §16): the
    #: transports register their static wire/reliability counters and
    #: the data plane's retry instants into it.  ``compare=False`` — the
    #: handle never participates in equality/hashing, so attaching
    #: telemetry cannot perturb jit cache keys or session specs.
    telemetry: Any = dataclasses.field(default=None, compare=False,
                                       repr=False)

    def __post_init__(self):
        if self.transport not in ("auto", "innetwork"):
            raise ValueError(f"unknown transport {self.transport!r}")
        if self.fault_plan is not None and self.transport != "innetwork":
            raise ValueError("fault_plan models the lossy switch fabric; "
                             "it needs transport='innetwork'")
        if self.transport == "innetwork":
            if self.algorithm != "auto":
                raise ValueError(
                    f"transport='innetwork' conflicts with algorithm="
                    f"{self.algorithm!r}: the switch data plane picks its "
                    "aggregation design by the §6.4 size switchover")
            if self.hierarchical is False:
                raise ValueError(
                    "transport='innetwork' is tree-driven by construction; "
                    "hierarchical=False cannot apply")
        if self.reproducible and self.compression != "none":
            raise ValueError("reproducible mode is incompatible with lossy "
                             "compression")
        if self.reproducible and self.sparse_k_frac > 0:
            raise ValueError("reproducible mode is incompatible with "
                             "sparsification")
        if self.compression not in ("none", "int8"):
            raise ValueError(f"unknown compression {self.compression!r}")
        if self.hierarchical and len(self.axes) < 2:
            raise ValueError("hierarchical=True needs a multi-axis mesh "
                             f"(axes={self.axes!r}); the tree has one level")
        # the force flag and an explicit dense algorithm must agree — a
        # silently-ignored force is worse than an error
        if (self.hierarchical is True
                and self.algorithm not in ("auto", "hierarchical")):
            raise ValueError(
                f"hierarchical=True conflicts with algorithm="
                f"{self.algorithm!r}; use algorithm='auto' or 'hierarchical'")
        if self.hierarchical is False and self.algorithm == "hierarchical":
            raise ValueError("hierarchical=False conflicts with "
                             "algorithm='hierarchical'")


class GradReducer:
    """Reduces a gradient pytree with the configured Flare algorithm.

    ``manager``/``tenant`` attach this reducer to a shared multi-tenant
    switch runtime (``runtime.SessionManager``, ``transport="innetwork"``
    only): each dtype arena group opens its own session — named
    ``{tenant}/{dtype}`` since tenants are per wire image — admitted
    against switch capacity, and reduces under the runtime's
    contention-derived packet arrival schedule (DESIGN.md §13).
    """

    def __init__(self, config: FlareConfig, *, manager=None,
                 tenant: str | None = None):
        self.config = config
        if manager is not None and config.transport != "innetwork":
            raise ValueError(
                "a runtime.SessionManager needs transport='innetwork'; "
                f"config has transport={config.transport!r}")
        self.manager = manager
        if manager is not None and tenant is None:
            # a stable auto-name per reducer: two reducers sharing a
            # manager must be distinct tenants even with equal shapes
            tenant = manager.new_tenant()
        self.tenant = tenant
        if config.sparse_k_frac > 0 and config.transport != "innetwork":
            # fail fast: sparse_allreduce's recursive doubling needs a
            # power-of-two inner axis, and a bad mesh shape should raise
            # here, not deep inside the traced schedule (the innetwork
            # data plane's coordinate merge is an iterated per-level fold
            # and has no such constraint).  When no ambient mesh is
            # installed yet the check defers to trace time.
            inner = config.axes[-1]
            p = compat.ambient_axis_size(inner)
            if p is not None and p & (p - 1):
                raise ValueError(
                    f"sparse_k_frac={config.sparse_k_frac} requires a "
                    f"power-of-two inner axis for the §7 recursive-doubling "
                    f"merge; mesh axis {inner!r} has size {p}")
            if config.hierarchical:
                # the hierarchical sparse merge continues the recursive
                # doubling across the outer axes too
                sizes = compat.ambient_axis_sizes(config.axes[:-1])
                if sizes is not None and any(s & (s - 1) for s in sizes):
                    raise ValueError(
                        "hierarchical sparse transport requires power-of-two "
                        f"outer axes; mesh axes {config.axes[:-1]!r} have "
                        f"sizes {sizes}")

    # -- error-feedback state ------------------------------------------------
    @property
    def needs_state(self) -> bool:
        c = self.config
        return c.compression != "none" or c.sparse_k_frac > 0

    def init_state(self, grads: Any) -> Any:
        """Zero EF residuals shaped like the gradient pytree (or None)."""
        if not self.needs_state:
            return None
        return jax.tree.map(lambda g: jnp.zeros(g.shape, g.dtype), grads)

    # -- the reduction -------------------------------------------------------
    def __call__(self, grads: Any, state: Any = None) -> tuple[Any, Any]:
        if self.config.arena:
            return self._reduce_arena(grads, state)
        return self._reduce_legacy(grads, state)

    def _world(self) -> int:
        return lax.axis_size(tuple(self.config.axes))

    def _transport(self, dtype, *, batched: bool):
        """Group transport; dtype-suffixed tenant names under a manager
        (each dtype arena is its own wire image, hence its own session)."""
        tenant = self.tenant
        if self.manager is not None and tenant is not None:
            tenant = f"{tenant}/{jnp.dtype(dtype).name}"
        return transports.from_config(self.config, dtype, batched=batched,
                                      manager=self.manager, tenant=tenant)

    def _pad_multiple(self, world: int) -> int:
        """Chunk-divisibility folded into the arena plan.

        ``2 · world`` covers ring (P), pipelined ring waves (2P), rhd (P)
        and the two-level inner/outer split; with int8 transport the
        quantization block rides along too, so every bucket chunk is a
        whole number of quant blocks — no runtime pad anywhere.
        """
        pad = 2 * world
        if self.config.compression == "int8":
            pad = math.lcm(pad, world * transports.QUANT_BLOCK)
        return pad

    # -- flat-arena pipelined path (the hot path) ----------------------------
    def _reduce_arena(self, grads: Any, state: Any) -> tuple[Any, Any]:
        c = self.config
        leaves, treedef = jax.tree.flatten(grads)
        ef_leaves = (jax.tree.flatten(state)[0] if state is not None
                     else None)
        plan = arena_mod.build_plan(leaves, c.bucket_bytes,
                                    pad_multiple=self._pad_multiple(
                                        self._world()))

        ef_out_groups: list[jax.Array | None] = []
        red_groups: list[jax.Array] = []
        for g in plan.groups:
            with jax.named_scope("flare.pack"):
                buf = g.pack(leaves)
                ef_buf = g.pack(ef_leaves) if ef_leaves is not None else None
            transport = self._transport(g.dtype, batched=True)
            red, ef_red = transport(buf, ef_buf, g.staggers(c.stagger),
                                    g.valid_extents)
            red_groups.append(red)
            ef_out_groups.append(ef_red)
        with jax.named_scope("flare.unpack"):
            out_leaves = plan.unpack(red_groups)

        out = jax.tree.unflatten(treedef, out_leaves)
        if not self.needs_state:
            return out, None
        with jax.named_scope("flare.unpack"):
            ef_flat = plan.unpack(
                [e if e is not None else jnp.zeros_like(r)
                 for e, r in zip(ef_out_groups, red_groups)])
        return out, jax.tree.unflatten(treedef, ef_flat)

    # -- per-bucket loop (benchmark baseline) --------------------------------
    def _reduce_legacy(self, grads: Any, state: Any) -> tuple[Any, Any]:
        """The seed dispatch loop, now a loop over B=1 transport groups."""
        c = self.config
        leaves, treedef = jax.tree.flatten(grads)
        ef_leaves = (jax.tree.flatten(state)[0] if state is not None
                     else [None] * len(leaves))
        buckets = bucketing.build_buckets(leaves, c.bucket_bytes, c.stagger)

        out_leaves: list[jax.Array | None] = [None] * len(leaves)
        new_ef: list[jax.Array | None] = [None] * len(leaves)

        for b in buckets:
            with jax.named_scope("flare.pack"):
                flat = bucketing.pack_bucket(leaves, b)
                ef_flat = (bucketing.pack_bucket(ef_leaves, b)
                           if self.needs_state else None)
            transport = self._transport(flat.dtype, batched=False)
            stagger = b.stagger if c.stagger else 0
            red, ef_out = transport(
                flat[None], ef_flat[None] if ef_flat is not None else None,
                jnp.full((1,), stagger, jnp.int32), (b.num_elements,))
            with jax.named_scope("flare.unpack"):
                for i, piece in bucketing.unpack_bucket(red[0], leaves, b):
                    out_leaves[i] = piece
                if ef_out is not None:
                    for i, piece in bucketing.unpack_bucket(ef_out[0], leaves,
                                                            b):
                        new_ef[i] = piece

        out = jax.tree.unflatten(treedef, out_leaves)
        state_out = (jax.tree.unflatten(treedef, new_ef)
                     if self.needs_state else None)
        return out, state_out
