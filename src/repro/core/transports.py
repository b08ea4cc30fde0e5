"""The unified transport layer: one batched wire schedule per dtype arena.

``GradReducer`` used to re-implement the sparse / int8 / dense three-way
dispatch twice (once for the flat-arena path, once for the legacy
per-bucket loop), and the sparse and int8 branches serialized the arena's
B buckets under a ``lax.scan`` — exactly the workloads the paper argues
benefit most from flexible aggregation (§7 sparse, F1 custom dtypes).
This module is the single home of that dispatch: a ``Transport`` reduces
a whole ``(B, S)`` dtype arena in one traced computation, with top-k +
error-feedback folded into the same trace.

=============  ============================================================
transport       batched wire schedule (per dtype group)
=============  ============================================================
``dense``       vmapped allreduce: every ring/rhd/tree round carries all B
                buckets' chunks in one collective — 2(P-1) or log P rounds
                total (PR 1's §6.2 multi-buffer schedule, unchanged).
``int8``        ``compression.quantized_allreduce_batched``: ONE
                ``all_to_all`` + ONE ``all_gather`` pair move every
                bucket's int8 payload — O(1) collectives per group.
``sparse``      ``sparse.sparse_allreduce_batched``: each recursive-
                doubling step issues ONE ppermute carrying all B buckets'
                coordinate lists — O(log P) collectives per group.
=============  ============================================================

Every transport also keeps its per-bucket ``lax.scan`` ancestor alive
behind ``batched=False`` — the bitwise-equality oracle for tests and the
scan-vs-batched baseline for ``benchmarks/run.py --quick``; per bucket
the combine chains are identical, so ``batched`` never changes results,
only how many collectives carry them.

Error feedback lives in exactly one place: every lossy transport routes
through ``compression.error_feedback_step`` with its own ``transmit``
closure (sparse returns the decoded top-k contribution, int8 the
quantize round-trip), and ``k`` for the sparse transport derives from
each bucket's **unpadded** extent via ``sparse.sparse_k`` — shared with
the legacy path, which is now just a B=1 loop over these same objects.

On multi-axis meshes every transport additionally picks a **flat vs
hierarchical** wire schedule (DESIGN.md §11): the mesh's reduction tree
(``topology.build_mesh_tree`` + ``transport_schedule``) decides at
trace time unless ``FlareConfig.hierarchical`` forces it.  Hierarchical
means the two-level in-network shape — dense reduce-scatters intra-pod
and reduces only ``Z/fanin`` across pods, int8 keeps the inter-pod
quantized legs at ``Z/fanin``, and sparse merges coordinate lists
intra-pod *before* the inter-pod exchange so the expensive hop carries
lists, not dense vectors.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import collectives as coll, compression, sparse, topology

#: Quantization block of the int8 transport; ``GradReducer`` folds
#: ``world * QUANT_BLOCK`` into the arena plan's pad multiple so every
#: bucket chunk is a whole number of quantization blocks (no runtime pad).
QUANT_BLOCK = 256


@dataclasses.dataclass(frozen=True)
class Transport:
    """Reduces one dtype's ``(B, S)`` arena in a single traced schedule.

    ``__call__(buf, ef, staggers, extents)``:
      * ``buf`` — the ``(B, S)`` arena buffer;
      * ``ef`` — error-feedback residuals of the same shape (or None);
      * ``staggers`` — per-bucket ring-phase offsets (§5), shape ``(B,)``;
      * ``extents`` — static per-bucket unpadded element counts from the
        arena plan (``DtypeArena.valid_extents``); k and other
        size-derived knobs come from these, never the padded S.

    Returns ``(reduced, ef_out)`` with ``ef_out`` None for lossless
    transports.
    """

    axes: tuple[str, ...]
    mean: bool = False
    batched: bool = True    # False → the per-bucket lax.scan ancestor
    #: flat vs hierarchical wire schedule.  ``None`` → the reduction
    #: tree decides (``topology.transport_schedule`` on the trace-time
    #: mesh tree); True/False force it (``FlareConfig.hierarchical``).
    hierarchical: bool | None = None
    #: ``repro.obs.Telemetry`` flight recorder (DESIGN.md §16).
    #: ``compare=False`` — attaching telemetry never changes a
    #: transport's identity, so jit cache keys and session specs are
    #: untouched.  The switch transport records its static counters and
    #: retry instants into it; the wire transports carry it for
    #: callers but add nothing themselves.
    telemetry: Any = dataclasses.field(default=None, compare=False,
                                       repr=False)

    @property
    def needs_state(self) -> bool:
        return False

    def _world(self) -> int:
        return lax.axis_size(tuple(self.axes))

    def _use_hierarchy(self) -> bool:
        """Resolve flat vs hierarchical at trace time, tree as arbiter."""
        if len(self.axes) < 2:
            return False
        if self.hierarchical is not None:
            return self.hierarchical
        sizes = tuple(lax.axis_size(a) for a in self.axes)
        tree = topology.build_mesh_tree(sizes)
        return topology.transport_schedule(tree) == "hierarchical"

    def __call__(self, buf: jax.Array, ef: jax.Array | None,
                 staggers: jax.Array, extents: Sequence[int],
                 ) -> tuple[jax.Array, jax.Array | None]:
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class DenseTransport(Transport):
    """Lossless allreduce of the arena — PR 1's vmapped schedule."""

    algorithm: str = "auto"
    reproducible: bool = False

    def _resolve(self, buf: jax.Array) -> str:
        alg = self.algorithm
        if alg == "auto":
            if self._use_hierarchy():
                # the mesh tree (or the config) chose the hierarchical
                # schedule: every size class rides the tree-driven path
                # (reproducible mode takes its fixed-tree variant).
                return "hierarchical"
            nbytes = buf.shape[1] * jnp.dtype(buf.dtype).itemsize
            alg = coll.select_algorithm(nbytes, reproducible=self.reproducible,
                                        multi_level=len(self.axes) > 1)
        return alg

    def __call__(self, buf, ef, staggers, extents):
        alg = self._resolve(buf)
        one = lambda v, s: coll.allreduce(
            v, self.axes, algorithm=alg, reproducible=self.reproducible,
            stagger=s)
        if self.batched:
            # all B buckets in one vmapped schedule: every collective
            # round carries the whole arena's worth of payload in one
            # batched ppermute/exchange (§6.2 multi-buffer parallelism).
            # Per bucket the combine chain is unchanged, so this is
            # bitwise-equal to the scan for every algorithm.
            red = jax.vmap(one)(buf, staggers)
        else:
            _, red = lax.scan(lambda _, xs: (None, one(*xs)), None,
                              (buf, staggers))
        if self.mean:
            red = red / self._world()
        return red, (jnp.zeros_like(ef) if ef is not None else None)


@dataclasses.dataclass(frozen=True)
class Int8Transport(Transport):
    """F1 int8 transport: quantized exchange with error feedback."""

    block: int = QUANT_BLOCK

    @property
    def needs_state(self) -> bool:
        return True

    @jax.named_scope("flare.int8")
    def __call__(self, buf, ef, staggers, extents):
        if ef is None:
            ef = jnp.zeros_like(buf)
        *outer_axes, inner = self.axes
        hier = self._use_hierarchy() and bool(outer_axes)
        # the hier functions walk upper tree levels leaf-first, so the
        # outer axes go innermost-first (mesh order is outermost-first)
        up_axes = tuple(reversed(outer_axes))

        if self.batched:
            def transmit(v):            # v: (B, S)
                if hier:
                    red = compression.quantized_allreduce_hier_batched(
                        v, inner, up_axes, block=self.block)
                else:
                    red = compression.quantized_allreduce_batched(
                        v, inner, block=self.block)
                    for ax in outer_axes:
                        red = compression.quantized_allreduce_batched(
                            red, ax, block=self.block)
                return red, compression.quantize_roundtrip(v, self.block)

            red, ef_out = compression.error_feedback_step(buf, ef, transmit)
        else:
            def body(_, xs):
                v, e, _s = xs

                def transmit(w):        # w: (S,)
                    if hier:
                        red = compression.quantized_allreduce_hier(
                            w, inner, up_axes, block=self.block)
                    else:
                        red = compression.quantized_allreduce(
                            w, inner, block=self.block)
                        for ax in outer_axes:
                            red = compression.quantized_allreduce(
                                red, ax, block=self.block)
                    return red, compression.quantize_roundtrip(w, self.block)

                return None, compression.error_feedback_step(v, e, transmit)

            _, (red, ef_out) = lax.scan(body, None, (buf, ef, staggers))
        if self.mean:
            red = red / self._world()
        return red, ef_out


@dataclasses.dataclass(frozen=True)
class SparseTransport(Transport):
    """§7 top-k sparse transport with densify-on-overflow + EF."""

    k_frac: float = 0.01
    density_threshold: float = 0.25

    @property
    def needs_state(self) -> bool:
        return True

    def _ks(self, extents: Sequence[int]) -> tuple[int, ...]:
        return tuple(sparse.sparse_k(self.k_frac, e) for e in extents)

    @jax.named_scope("flare.sparse")
    def __call__(self, buf, ef, staggers, extents):
        if ef is None:
            ef = jnp.zeros_like(buf)
        *outer_axes, inner = self.axes
        p = lax.axis_size(inner)
        if p & (p - 1):
            raise ValueError(
                f"sparse transport requires a power-of-two inner axis; "
                f"mesh axis {inner!r} has size {p}")
        ks = self._ks(extents)
        hier = self._use_hierarchy() and bool(outer_axes)
        # upper tree levels run leaf-first: outer axes innermost-first
        up_axes = tuple(reversed(outer_axes))
        if hier:
            # the hierarchical merge continues the recursive doubling
            # across the outer axes, so those must be powers of two as
            # well.  Auto mode (hierarchical=None) quietly keeps such
            # meshes on the two_level schedule (dense across pods works
            # for any outer size — the pre-hierarchy behavior); an
            # explicit hierarchical=True is a config error.
            bad = [a for a in outer_axes
                   if lax.axis_size(a) & (lax.axis_size(a) - 1)]
            if bad and self.hierarchical:
                raise ValueError(
                    f"hierarchical sparse transport requires power-of-two "
                    f"outer axes; mesh axes {bad!r} are not")
            hier = not bad

        if self.batched:
            def transmit(v):            # v: (B, S)
                if hier:
                    # lists stay sparse across the inter-pod hop
                    return sparse.sparse_allreduce_hier_batched(
                        v, inner, up_axes, ks,
                        density_threshold=self.density_threshold)
                if outer_axes:
                    return sparse.sparse_allreduce_two_level_batched(
                        v, inner, outer_axes[-1], ks,
                        density_threshold=self.density_threshold)
                return sparse.sparse_allreduce_batched(
                    v, inner, ks, density_threshold=self.density_threshold)

            red, ef_out = compression.error_feedback_step(buf, ef, transmit)
        else:
            k_max = max(ks)
            ks_arr = jnp.asarray(ks, jnp.int32)

            def body(_, xs):
                v, e, _s, ke = xs

                def transmit(w):        # w: (S,)
                    if hier:
                        return sparse.sparse_allreduce_hier(
                            w, inner, up_axes, k_max,
                            density_threshold=self.density_threshold,
                            k_eff=ke)
                    if outer_axes:
                        return sparse.sparse_allreduce_two_level(
                            w, inner, outer_axes[-1], k_max,
                            density_threshold=self.density_threshold,
                            k_eff=ke)
                    return sparse.sparse_allreduce(
                        w, inner, k_max,
                        density_threshold=self.density_threshold, k_eff=ke)

                return None, compression.error_feedback_step(v, e, transmit)

            _, (red, ef_out) = lax.scan(body, None, (buf, ef, staggers,
                                                     ks_arr))
        if self.mean:
            red = red / self._world()
        return red, ef_out


@dataclasses.dataclass(frozen=True)
class SwitchTransport(Transport):
    """The fourth transport: the emulated sPIN switch data plane.

    ``FlareConfig(transport="innetwork")`` routes each arena group
    leaf → switch → leaf on the mesh's reduction tree
    (``repro.switch.dataplane``): hosts frame the ``(B, S)`` arena into
    MTU packets, a designated switch rank per tree level aggregates them
    with the installed handler (dense fp32 sum, bitwise fixed-tree,
    int8 dequant-accumulate, or §7 sparse coordinate-merge) under one of
    the §6.1–§6.3 buffer designs, and the root multicasts the result
    back down.  ``mode`` picks the handler family; ``design="auto"``
    follows the §6.4 size switchover (``perfmodel.select_design``), and
    ``reproducible`` pins the fixed-tree handler (always tree
    aggregation, §6.4).

    The schedule is inherently tree-driven — the ``hierarchical`` knob
    of the wire transports doesn't apply (packets carry their block id,
    so B buckets always share the wire).  ``batched`` (inherited from
    :class:`Transport`, default True) picks the data-plane schedule:
    the batched plane runs each tree level as a few collectives +
    slot-axis kernels over the packed packet tensor, ``batched=False``
    keeps the per-slot/per-hop loop as the bitwise oracle — the two are
    cross-checked bit for bit in the multidevice ``switch`` group.
    """

    mode: str = "dense"             # dense | int8 | sparse
    reproducible: bool = False
    design: str = "auto"            # §6.1-§6.3 buffer design, auto = §6.4
    block: int = QUANT_BLOCK
    k_frac: float = 0.01
    density_threshold: float = 0.25
    #: multi-tenant attachment (DESIGN.md §13): a ``runtime.
    #: SessionManager`` shared by several reducers in one process.  At
    #: trace time the transport opens/attaches its session (admission
    #: control against switch capacity — ``runtime.AdmissionError``
    #: propagates to the caller as the host-fallback signal) and the
    #: data plane runs under the manager's contention-derived arrival
    #: permutations for this ``tenant``.  ``None`` → the single-job
    #: plane of PR 4, unchanged.
    manager: Any = dataclasses.field(default=None, compare=False)
    tenant: str | None = None
    #: deterministic lossy-fabric injection (``switch.packets.FaultPlan``,
    #: DESIGN.md §14).  A surviving plan runs in-network — the
    #: reliability layer recovers every packet, bitwise.  A plan the
    #: retry budget cannot recover is detected *statically* before
    #: tracing (``dataplane.plan_survives``): this session alone degrades
    #: to the matching wire transport, draining from the shared runtime
    #: via ``ft.recover_session_failure``.
    fault_plan: Any = None

    @property
    def needs_state(self) -> bool:
        return self.mode in ("int8", "sparse")

    def _session_perms(self, buf, k: int | None = None):
        """Attach to the shared switch; returns this tenant's per-level
        arrival permutations (``None`` when alone on an idle switch)."""
        if self.manager is None:
            return None
        sess = self.manager.attach(
            self.tenant, mode=self.mode, num_buckets=buf.shape[0],
            bucket_elems=buf.shape[1], dtype=buf.dtype,
            reproducible=self.reproducible, design=self.design, k=k,
            axes=self.axes, fault_plan=self.fault_plan)
        return self.manager.arrival_perms(sess.tenant)

    def _plan_survives(self, buf, ks) -> bool:
        """Static retry-budget pre-check on this arena's level shapes."""
        from repro.switch import dataplane

        fanins = [l.fanin for l in dataplane._levels(self.axes)]
        counts = dataplane.level_packet_counts(
            fanins, int(buf.shape[0]), int(buf.shape[1]), buf.dtype,
            mode=self.mode, block=self.block,
            k_max=max(ks) if ks else None,
            density_threshold=self.density_threshold)
        return dataplane.plan_survives(self.fault_plan, counts)

    def _record_solo(self, buf, ks) -> None:
        """Solo (manager-less) flight recording: register the static
        wire/reliability counters this trace will execute.  Under a
        manager the session's *admission* records the same sums exactly
        once, so the two paths never double-count."""
        if self.telemetry is None or self.manager is not None:
            return
        from repro.switch import dataplane

        tenant = self.tenant or "solo"
        b, s = int(buf.shape[0]), int(buf.shape[1])
        if self.mode == "dense":
            wire_dtype, elems = buf.dtype, s
        elif self.mode == "int8":
            wire_dtype, elems = jnp.int8, s + (-s) % self.block
        else:
            wire_dtype, elems = jnp.int32, 2 * max(ks)
        sizes = tuple(lax.axis_size(a) for a in self.axes)
        self.telemetry.record_switch_counters(
            tenant, dataplane.plan_counters(
                self.axes, sizes, b, elems, wire_dtype,
                design=self.design, reproducible=self.reproducible))
        if self.fault_plan is not None:
            fanins = [l.fanin for l in dataplane._levels(self.axes)]
            counts = dataplane.level_packet_counts(
                fanins, b, s, buf.dtype, mode=self.mode, block=self.block,
                k_max=max(ks) if ks else None,
                density_threshold=self.density_threshold)
            self.telemetry.record_fault_schedules(
                tenant, dataplane.fault_schedules(self.fault_plan, counts))

    def _degrade(self) -> Transport:
        """Retry budget exhausted: drain this session from the shared
        runtime and hand the arena to the matching wire transport (the
        host-fallback leg of ``ft.recover_session_failure``).  Only this
        session degrades — other tenants keep the switch."""
        from repro.ft import coordinator as ft

        if self.manager is not None:
            ft.recover_session_failure(self.manager, self.tenant)
        if self.mode == "sparse":
            return SparseTransport(self.axes, mean=self.mean, batched=True,
                                   k_frac=self.k_frac,
                                   density_threshold=self.density_threshold)
        if self.mode == "int8":
            return Int8Transport(self.axes, mean=self.mean, batched=True,
                                 block=self.block)
        return DenseTransport(self.axes, mean=self.mean, batched=True,
                              reproducible=self.reproducible)

    def __call__(self, buf, ef, staggers, extents):
        from repro.switch import dataplane

        ks = (tuple(sparse.sparse_k(self.k_frac, e) for e in extents)
              if self.mode == "sparse" else None)
        if self.fault_plan is not None and not self._plan_survives(buf, ks):
            return self._degrade()(buf, ef, staggers, extents)
        self._record_solo(buf, ks)

        if self.mode == "dense":
            red = dataplane.switch_allreduce_dense(
                buf, self.axes, reproducible=self.reproducible,
                design=self.design,
                arrival_perms=self._session_perms(buf),
                fault_plan=self.fault_plan, batched=self.batched,
                telemetry=self.telemetry, tenant=self.tenant)
            if self.mean:
                red = red / self._world()
            return red, (jnp.zeros_like(ef) if ef is not None else None)

        if ef is None:
            ef = jnp.zeros_like(buf)
        if self.mode == "int8":
            perms = self._session_perms(buf)

            def transmit(v):
                red = dataplane.switch_allreduce_int8(
                    v, self.axes, block=self.block, design=self.design,
                    arrival_perms=perms, fault_plan=self.fault_plan,
                    batched=self.batched,
                    telemetry=self.telemetry, tenant=self.tenant)
                return red, compression.quantize_roundtrip(v, self.block)
        elif self.mode == "sparse":
            perms = self._session_perms(buf, k=max(ks))

            def transmit(v):
                return dataplane.switch_allreduce_sparse(
                    v, self.axes, ks,
                    density_threshold=self.density_threshold,
                    arrival_perms=perms, fault_plan=self.fault_plan,
                    batched=self.batched,
                    telemetry=self.telemetry, tenant=self.tenant)
        else:
            raise ValueError(f"unknown switch transport mode {self.mode!r}")
        red, ef_out = compression.error_feedback_step(buf, ef, transmit)
        if self.mean:
            red = red / self._world()
        return red, ef_out


def _switch_from_config(config, dtype, is_float: bool, *,
                        batched: bool = True,
                        manager=None, tenant=None,
                        telemetry=None) -> SwitchTransport:
    axes = tuple(config.axes)
    fault_plan = getattr(config, "fault_plan", None)
    if config.sparse_k_frac > 0 and is_float:
        return SwitchTransport(axes, mean=config.mean, batched=batched,
                               telemetry=telemetry,
                               mode="sparse",
                               k_frac=config.sparse_k_frac,
                               density_threshold=config.density_threshold,
                               manager=manager, tenant=tenant,
                               fault_plan=fault_plan)
    if config.compression == "int8" and is_float:
        return SwitchTransport(axes, mean=config.mean, batched=batched,
                               telemetry=telemetry,
                               mode="int8",
                               manager=manager, tenant=tenant,
                               fault_plan=fault_plan)
    return SwitchTransport(axes, mean=config.mean, batched=batched,
                           telemetry=telemetry,
                           mode="dense",
                           reproducible=config.reproducible,
                           manager=manager, tenant=tenant,
                           fault_plan=fault_plan)


def from_config(config, dtype, *, batched: bool = True,
                manager=None, tenant: str | None = None) -> Transport:
    """The transport dispatch, in one place.

    ``config`` is any object with the ``FlareConfig`` transport fields
    (axes, algorithm, reproducible, compression, sparse_k_frac,
    density_threshold, mean, hierarchical, transport).  Lossy transports
    apply to floating dtypes only; everything else rides the dense path.
    ``transport="innetwork"`` swaps the wire schedules for the emulated
    switch data plane (``SwitchTransport``) while keeping the same
    dense/int8/sparse handler selection; a shared ``manager``
    (``runtime.SessionManager``) additionally attaches the transport as
    tenant ``tenant`` of the multi-tenant switch runtime — admission
    control plus contention-derived packet arrival schedules (DESIGN.md
    §13).  The flat-vs-hierarchical choice threads through to every wire
    transport: ``hierarchical=None`` lets the mesh's reduction tree
    decide at trace time (``topology.transport_schedule``).
    """
    axes = tuple(config.axes)
    hierarchical = getattr(config, "hierarchical", None)
    telemetry = getattr(config, "telemetry", None)
    is_float = jnp.issubdtype(jnp.dtype(dtype), jnp.floating)
    if getattr(config, "transport", "auto") == "innetwork":
        return _switch_from_config(config, dtype, is_float, batched=batched,
                                   manager=manager, tenant=tenant,
                                   telemetry=telemetry)
    if manager is not None:
        raise ValueError(
            "a runtime.SessionManager applies to transport='innetwork' "
            f"only; config has "
            f"transport={getattr(config, 'transport', 'auto')!r}")
    if config.sparse_k_frac > 0 and is_float:
        return SparseTransport(axes, mean=config.mean, batched=batched,
                               hierarchical=hierarchical,
                               telemetry=telemetry,
                               k_frac=config.sparse_k_frac,
                               density_threshold=config.density_threshold)
    if config.compression == "int8" and is_float:
        return Int8Transport(axes, mean=config.mean, batched=batched,
                             hierarchical=hierarchical, telemetry=telemetry)
    return DenseTransport(axes, mean=config.mean, batched=batched,
                          hierarchical=hierarchical, telemetry=telemetry,
                          algorithm=config.algorithm,
                          reproducible=config.reproducible)
