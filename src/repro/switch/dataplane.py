"""The emulated switch data plane: ingress → aggregate → multicast (§4).

Runs the Flare switch loop *functionally* on mesh wires, inside a
``shard_map`` manual region.  Per level of the mesh's reduction tree
(``topology.mesh_levels``):

  1. **ingress** — every child frames its ``(B, S)`` arena into MTU
     packets (``packets.packetize``) and streams them to the level's
     designated switch rank (``MeshLevel.switch_rank``, rank 0 of the
     axis group — the paper's leaf/root switch).  The wire realization
     is the existing ring ``ppermute`` math (``collectives.
     ring_all_gather``); SPMD obliges every rank to materialize the
     child stack, but only the switch rank's aggregate survives the
     mask, so the data the hosts end with really did flow
     host → switch → host.
  2. **aggregate** — the installed sPIN handler triple runs over the
     child-stacked packets (``handlers``): header steering (arrival vs
     child order), the payload combine under one of the §6.1–§6.3
     buffer designs, completion.  An optional per-level *arrival
     permutation* reorders the ingress streams first — the adversarial
     schedule the reproducibility tests drive.
  3. the aggregated block is forwarded up the next tree level (child
     rank = this rank's index on that axis), and after the root, the
     result **multicasts** back down every level — a binomial (XOR)
     broadcast tree from the switch rank, ``log2 P`` ``ppermute`` hops
     (ring broadcast on non-power-of-two fan-ins).

``plan_counters`` precomputes the packet/combine/buffer counts this
plane will execute — the same quantities (``P``, ``N``, per-design
combine and buffer counts) the analytic model ``perfmodel.switch_model``
consumes, cross-checked in ``tests/test_switch.py``.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import collectives as coll
from repro.core import compression, sparse, topology
from repro.kernels import ops
from repro.perfmodel import switch_model as sm
from repro.switch import handlers as hd
from repro.switch import packets as pk

DEFAULT_FORMAT = pk.PacketFormat()


def resolve_design(data_bytes: int, design: str = "auto",
                   reproducible: bool = False) -> tuple[str, int]:
    """The §6.4 design switchover for one reduction block.

    ``auto`` follows ``perfmodel.switch_model.select_design`` on the
    block size; reproducible mode always takes tree aggregation (§6.4).
    Returns ``(design, n_bufs)``.
    """
    if reproducible:
        return "tree", 1
    if design == "auto":
        return sm.select_design(data_bytes)
    if design not in hd.DESIGNS:
        raise ValueError(f"unknown aggregation design {design!r}")
    return design, (4 if design == "multi" else 1)


def _levels(axes: Sequence[str]) -> tuple[topology.MeshLevel, ...]:
    sizes = tuple(lax.axis_size(a) for a in axes)
    return topology.mesh_levels(tuple(axes), sizes)


class _PlaneObs:
    """Trace-time phase spans of one data-plane build (DESIGN.md §16).

    Spans land on the ``"trace"`` process, track ``plane/<tenant>`` —
    they wrap *tracing*, never add ops to the traced program, so the
    compiled computation is byte-identical with or without telemetry
    (the observability overhead contract).  ``telemetry=None`` degrades
    every phase to a ``nullcontext``.
    """

    def __init__(self, telemetry, tenant):
        self._tracer = None if telemetry is None else telemetry.tracer
        self._track = f"plane/{tenant}" if tenant else "plane/solo"

    def __call__(self, name, **args):
        if self._tracer is None:
            return contextlib.nullcontext()
        return self._tracer.span(name, track=self._track, process="trace",
                                 args=args or None)

    def instant(self, name, **args):
        if self._tracer is not None:
            self._tracer.instant(name, track=self._track, process="trace",
                                 args=args or None)

    def retries(self, faults):
        """One instant per faulted level: the static retry rounds the
        reliability layer will execute (mirrors ``FaultSchedule``)."""
        for i, f in enumerate(faults):
            if f is not None:
                self.instant(f"plane.retry.l{i + 1}", rounds=int(f.rounds),
                             retransmits=int(f.retransmits),
                             wait_rounds=float(f.wait_rounds))


# ---------------------------------------------------------------------------
# Wire primitives: ingress gather and root multicast.
# ---------------------------------------------------------------------------

def _gather_children(tree: Any, axis: str) -> Any:
    """Stack every child's leaves along a new leading axis: leaf
    ``(n, ...)`` → ``(P, n, ...)`` with slot ``c`` = child ``c``'s copy.

    The wire is the existing ring all-gather (P−1 ``ppermute`` hops);
    ``stagger=-1`` pins slot order to child rank so the stack arrives in
    canonical order before any arrival permutation is applied.
    """
    p = lax.axis_size(axis)

    def g(leaf):
        flat = coll.ring_all_gather(leaf, axis, stagger=-1)
        return flat.reshape((p,) + leaf.shape)

    return jax.tree.map(g, tree)


def _multicast(tree: Any, axis: str, switch_rank: int = 0) -> Any:
    """Broadcast the switch rank's leaves to every child of the level.

    Power-of-two fan-in: binomial XOR tree rooted at ``switch_rank``
    (log2 P ``ppermute`` hops — the root multicast down the reduction
    tree).  Otherwise a ring broadcast (P−1 hops).  Non-switch ranks'
    payloads are masked zeros and are simply overwritten.
    """
    p = lax.axis_size(axis)
    if p == 1:
        return tree
    r = lax.axis_index(axis)
    root = switch_rank % p
    r_rel = (r - root) % p
    if p & (p - 1) == 0:
        for k in range(p.bit_length() - 1):
            d = 1 << k
            perm = [((root + i) % p, (root + (i ^ d)) % p) for i in range(p)]
            recv = jax.tree.map(
                lambda l: lax.ppermute(l, axis, perm), tree)
            keep = (r_rel >= d) & (r_rel < 2 * d)
            tree = jax.tree.map(lambda a, b: jnp.where(keep, b, a),
                                tree, recv)
    else:
        perm = [((root + i) % p, (root + i + 1) % p) for i in range(p)]
        for s in range(p - 1):
            recv = jax.tree.map(lambda l: lax.ppermute(l, axis, perm), tree)
            tree = jax.tree.map(lambda a, b: jnp.where(r_rel == s + 1, b, a),
                                tree, recv)
    return tree


def _mask_to_switch(tree: Any, axis: str, switch_rank: int) -> Any:
    """Zero every rank's leaves except the level's designated switch."""
    r = lax.axis_index(axis)
    return jax.tree.map(
        lambda l: jnp.where(r == switch_rank, l, jnp.zeros_like(l)), tree)


def _apply_arrival(stack: Any, headers: jax.Array,
                   perm: np.ndarray | Sequence[int] | None,
                   ) -> tuple[Any, jax.Array]:
    """Reorder the child streams by a static arrival permutation.

    ``perm`` is ``(P,)`` (whole streams arrive out of order) or
    ``(P, n)`` (each packet slot sees its own interleaving — the fully
    adversarial schedule), or a **callable** ``(P, n) -> perm`` resolved
    at trace time — how the multi-tenant runtime supplies contention-
    derived permutations without knowing each level's packet count up
    front (the sparse plane's list capacity, and hence ``n``, grows per
    level).  Headers ride along so child-order handlers can undo it.
    """
    if perm is None:
        return stack, headers
    if callable(perm):
        perm = perm(int(headers.shape[0]), int(headers.shape[1]))
        if perm is None:
            return stack, headers
    order = jnp.asarray(np.asarray(perm), jnp.int32)
    if order.ndim == 1:
        order = jnp.broadcast_to(order[:, None],
                                 (order.shape[0], headers.shape[1]))
    stack = jax.tree.map(lambda l: hd.apply_order(l, order), stack)
    return stack, hd.apply_order(headers, order)


# ---------------------------------------------------------------------------
# Batched wire primitives (DESIGN.md §12): whole-stack ingress + row-pick
# multicast.  The slot-loop path above realizes the same data movement as
# P−1 ring hops / log P binomial hops; these express it as one collective
# per level, which is what closes the emulator's overhead gap.
# ---------------------------------------------------------------------------

def _all_gather_stack(leaf: jax.Array, axis: str) -> jax.Array:
    """Child-stacked ingress in one collective: ``(n, ...)`` →
    ``(P, n, ...)`` with slot ``c`` = child ``c``'s copy — bitwise the
    same stack ``_gather_children`` assembles from P−1 ring hops."""
    return lax.all_gather(leaf, axis, axis=0, tiled=False)


def _multicast_root(tree: Any, levels: Sequence[topology.MeshLevel]) -> Any:
    """Root multicast down every level in one collective per level.

    The binomial ``_multicast`` chain relays the switch rank's bits
    unchanged (every hop overwrites, never combines), so its fixpoint is
    simply "every rank holds the switch rank's leaves" — which one
    all-gather + static row-pick per level produces bit for bit.
    """
    for lvl in reversed(levels):
        tree = jax.tree.map(
            lambda l: _all_gather_stack(l, lvl.axis)[lvl.switch_rank], tree)
    return tree


def _resolve_perm(perm, p: int, n: int) -> np.ndarray | None:
    """Materialize an arrival permutation as a static ``(P, n)`` order."""
    if perm is None:
        return None
    if callable(perm):
        perm = perm(p, n)
        if perm is None:
            return None
    perm = np.asarray(perm, np.int32)
    if perm.ndim == 1:
        perm = np.broadcast_to(perm[:, None], (p, n))
    return perm


def _steered(handler: hd.Handler) -> bool:
    return handler.header_handler in (hd.child_order, hd.child_order_opt)


def _net_order(handler: hd.Handler, arrival, p: int,
               n: int) -> np.ndarray | None:
    """The *net* stack order after arrival interleave ∘ header steering,
    composed statically at trace time.

    Arrival permutations are static (or trace-time callables) and header
    steering is ``argsort(HDR_CHILD)`` of statically-known headers, so
    the batched path never materializes permuted headers: for a
    child-steered handler the argsort is the exact inverse of any
    arrival permutation (child ids are distinct per slot), net identity;
    for an arrival-order handler the net order is the permutation
    itself.
    """
    if _steered(handler):
        return None
    return _resolve_perm(arrival, p, n)


def _batched_admission(sched: pk.FaultSchedule, stats: dict) -> np.ndarray:
    """Vectorized replay of a level's fault schedule — the per-(block,
    child) accept masks of every round folded into static numpy tensors.

    The slot-loop ``_reliable_ingress`` is exactly-once by construction:
    when the schedule survives, the recovered stack equals the clean
    gathered stack bit for bit, and every traced counter is a pure
    function of the schedule's masks (the chaos anchor pins traced ==
    static).  So the batched path evaluates those mask folds in numpy —
    clean = arrives ∧ ¬corrupt, seen = any clean delivery so far — and
    emits the counters as constants:

    * ``corrupt_rejected``: every corrupted delivery fails the checksum,
      ``Σ corrupt``;
    * ``duplicates_dropped``: a clean delivery of an already-seen slot,
      ``Σ (clean ∧ seen_before)``;
    * ``delivered``: slots seen after the final round (= P·n iff the
      schedule survives).

    Returns the final ``(P, n)`` delivered mask, which the caller folds
    into the gathered stack (``fold_once``) — all-ones on a surviving
    schedule, so admission never perturbs bits.
    """
    if not sched.survives:
        raise FaultBudgetExceeded(
            f"fault schedule loses packets beyond the retry budget "
            f"({sched.rounds} rounds, {sched.retransmits} retransmits)")
    arrives = np.asarray(sched.arrives)
    corrupt = np.asarray(sched.corrupt)
    clean = arrives & ~corrupt
    seen_after = np.cumsum(clean, axis=0) > 0
    seen_before = np.zeros_like(seen_after)
    seen_before[1:] = seen_after[:-1]
    stats["corrupt_rejected"] += jnp.int32(int(corrupt.sum()))
    stats["duplicates_dropped"] += jnp.int32(int((clean & seen_before).sum()))
    stats["retransmits"] += jnp.int32(sched.retransmits)
    stats["delivered"] += jnp.int32(int(seen_after[-1].sum()))
    stats["wait_rounds"] += jnp.int32(round(sched.wait_rounds))
    return seen_after[-1]


def _admit(stack: Any, fault: pk.FaultSchedule | None,
           fault_stats: dict) -> Any:
    """Apply a level's batched admission mask to the gathered stack."""
    if fault is None:
        return stack
    mask = jnp.asarray(_batched_admission(fault, fault_stats))
    return jax.tree.map(
        lambda l: hd.fold_once(jnp.zeros_like(l), l, mask), stack)


# ---------------------------------------------------------------------------
# Reliability layer (DESIGN.md §14): lossy ingress + exactly-once recovery.
# ---------------------------------------------------------------------------

class FaultBudgetExceeded(RuntimeError):
    """A fault plan loses packets the retry budget cannot recover.

    Raised at trace time (survival is statically known — corruption
    deterministically fails the checksum, so the set of accepted packets
    is a pure function of the schedule).  The transport layer pre-checks
    with :func:`plan_survives` and degrades the session to the wire
    transport instead of ever tracing a non-surviving plane."""


def _new_fault_stats() -> dict:
    z = jnp.zeros((), jnp.int32)
    return {"retransmits": z, "duplicates_dropped": z,
            "corrupt_rejected": z, "delivered": z, "wait_rounds": z}


def _reliable_ingress(stack: Any, headers: jax.Array,
                      sched: pk.FaultSchedule,
                      stats: dict) -> tuple[Any, jax.Array]:
    """Replay a level's fault schedule and rebuild the clean canonical
    child stack, exactly once per packet.

    Each delivery round: the round's packets arrive (possibly
    bit-corrupted on the wire, possibly interleaved across children),
    header steering un-permutes them by ``HDR_CHILD``, the checksum
    header gates out corrupted payloads, and the seen-bitmap admits each
    ``(child, packet)`` slot at most once (``handlers.accept_mask`` /
    ``fold_once``) — duplicates and redundant retransmissions are
    no-ops.  Corruption targets the first leaf of the payload pytree
    (the checksummed stream whose headers ride the stack; sidebands
    fate-share via the shared accept mask).  If the schedule does not
    recover every packet within the retry budget the slot can never
    complete — :class:`FaultBudgetExceeded`."""
    if not sched.survives:
        raise FaultBudgetExceeded(
            f"fault schedule loses packets beyond the retry budget "
            f"({sched.rounds} rounds, {sched.retransmits} retransmits)")
    leaves, treedef = jax.tree.flatten(stack)
    p, n = int(headers.shape[0]), int(headers.shape[1])
    seen = jnp.zeros((p, n), bool)
    acc = [jnp.zeros_like(l) for l in leaves]
    acc_hdr = jnp.zeros_like(headers)
    for r in range(sched.rounds):
        arrives = jnp.asarray(sched.arrives[r])
        any_corrupt = bool(np.asarray(sched.corrupt[r]).any())
        if any_corrupt:
            # wire leg: corrupt the checksummed stream's masked packets
            corrupt = jnp.asarray(sched.corrupt[r])
            lvs = ([pk.corrupt_first_elem(leaves[0], corrupt)]
                   + list(leaves[1:]))
        else:
            lvs = list(leaves)
        hdr_r = headers
        perm = np.asarray(sched.perms[r])
        if not np.array_equal(perm, np.arange(p)):
            # the round's streams arrive interleaved; steer them back by
            # the CHILD header, never by arrival position
            order = jnp.broadcast_to(
                jnp.asarray(perm, jnp.int32)[:, None], (p, n))
            lvs = [hd.apply_order(l, order) for l in lvs]
            hdr_r = hd.apply_order(headers, order)
            back = hd.child_order(hdr_r)
            lvs = [hd.apply_order(l, back) for l in lvs]
            hdr_r = hd.apply_order(hdr_r, back)
        if any_corrupt:
            ok = pk.payload_checksum(lvs[0]) == hdr_r[:, :, pk.HDR_CSUM]
        else:
            # injection is the only corruption source in the emulation —
            # with none scheduled this round the verify is statically a
            # pass, so skip the checksum work (mirrors hardware CRC
            # offload: the host path doesn't recompute clean frames)
            ok = jnp.ones((p, n), bool)
        accept = hd.accept_mask(arrives, ok, seen)
        acc = [hd.fold_once(a, l, accept) for a, l in zip(acc, lvs)]
        acc_hdr = hd.fold_once(acc_hdr, hdr_r, accept)
        stats["corrupt_rejected"] += jnp.sum(arrives & ~ok, dtype=jnp.int32)
        stats["duplicates_dropped"] += jnp.sum(arrives & ok & seen,
                                               dtype=jnp.int32)
        seen = seen | (arrives & ok)
    stats["retransmits"] += jnp.int32(sched.retransmits)
    stats["delivered"] += jnp.sum(seen, dtype=jnp.int32)
    stats["wait_rounds"] += jnp.int32(round(sched.wait_rounds))
    return jax.tree.unflatten(treedef, acc), acc_hdr


def level_packet_counts(level_fanins: Sequence[int], num_buckets: int,
                        bucket_elems: int, dtype, *, mode: str = "dense",
                        fmt: pk.PacketFormat = DEFAULT_FORMAT,
                        block: int = 256, k_max: int | None = None,
                        density_threshold: float = 0.25,
                        ) -> list[tuple[int, int]]:
    """Per up-hop ``(fanin, packets per child)`` for one plane's schedule.

    The fault plan keys its per-level schedules on these shapes, so this
    is the single source of truth shared by the planes (which inject)
    and the transport layer (which pre-checks survival): dense streams a
    constant ``B · ceil(S/N)`` packets per level, int8 frames the
    quantized (block-padded) arena, and the sparse plane's packed
    coordinate lists grow ``cap *= fanin`` per level until the density
    threshold trips and it continues as dense fp32."""
    if mode == "dense":
        n = num_buckets * fmt.packets_per_block(bucket_elems, dtype)
        return [(p, n) for p in level_fanins]
    if mode == "int8":
        s = bucket_elems + (-bucket_elems) % block
        n = num_buckets * fmt.packets_per_block(s, jnp.int8)
        return [(p, n) for p in level_fanins]
    if mode == "sparse":
        if k_max is None:
            raise ValueError("sparse level_packet_counts needs k_max")
        out, cap, dense = [], int(k_max), False
        for p in level_fanins:
            if not dense and sparse.densify_step(cap * p, bucket_elems,
                                                 density_threshold):
                dense = True
            if dense:
                n = num_buckets * fmt.packets_per_block(bucket_elems,
                                                        jnp.float32)
            else:
                n = num_buckets * fmt.packets_per_block(2 * cap, jnp.int32)
                cap *= p
            out.append((p, n))
        return out
    raise ValueError(f"unknown plane mode {mode!r}")


def fault_schedules(plan: "pk.FaultPlan | None",
                    counts: Sequence[tuple[int, int]],
                    ) -> list["pk.FaultSchedule | None"]:
    """One schedule per level (``None`` where the plan doesn't apply)."""
    if plan is None:
        return [None] * len(counts)
    return [plan.schedule(i, p, n) if plan.applies(i) else None
            for i, (p, n) in enumerate(counts)]


def plan_survives(plan: "pk.FaultPlan | None",
                  counts: Sequence[tuple[int, int]]) -> bool:
    """Static pre-check: does every level recover within the budget?

    Deterministic in (plan, level shapes) — exactly the schedules the
    plane will replay — so the transport can decide *before tracing*
    whether to run in-network or degrade the session to the wire."""
    return all(s is None or s.survives
               for s in fault_schedules(plan, counts))


# ---------------------------------------------------------------------------
# Dense / fixed-tree data plane.
# ---------------------------------------------------------------------------

def _dense_level(arena: jax.Array, lvl: topology.MeshLevel,
                 handler: hd.Handler, design: str, n_bufs: int,
                 fmt: pk.PacketFormat, arrival,
                 fault: pk.FaultSchedule | None = None,
                 fault_stats: dict | None = None) -> jax.Array:
    """One up-hop: frame, stream to the switch, aggregate, mask."""
    b, s = arena.shape
    r = lax.axis_index(lvl.axis)
    stream = pk.packetize(arena, fmt, child_rank=r)
    stacked = _gather_children(stream, lvl.axis)
    payload, headers = stacked.payload, stacked.headers
    if fault is not None:
        payload, headers = _reliable_ingress(payload, headers, fault,
                                             fault_stats)
    payload, headers = _apply_arrival(payload, headers, arrival)
    egress, _ = hd.run(handler, payload, headers, design=design,
                       n_bufs=n_bufs, ctx={"dtype": arena.dtype})
    e = fmt.payload_elems(arena.dtype)
    npkt = fmt.packets_per_block(s, arena.dtype)
    out = egress.reshape(b, npkt * e)[:, :s]
    return _mask_to_switch(out, lvl.axis, lvl.switch_rank)


def _multicast_arena(arena: jax.Array, lvl: topology.MeshLevel,
                     fmt: pk.PacketFormat) -> jax.Array:
    """One down-hop: the switch multicasts its framed result."""
    b, s = arena.shape
    stream = pk.packetize(arena, fmt, child_rank=lvl.switch_rank)
    stream = _multicast(stream, lvl.axis, lvl.switch_rank)
    return pk.depacketize(stream, fmt, b, s)


def _dense_level_batched(arena: jax.Array, lvl: topology.MeshLevel,
                         handler: hd.Handler, design: str, n_bufs: int,
                         plan: pk.FramePlan, arrival,
                         fault: pk.FaultSchedule | None = None,
                         fault_stats: dict | None = None) -> jax.Array:
    """One up-hop as a few batched operations over the packed tensor.

    The framing plan packs the arena into the canonical ``(n, E)`` slot
    tensor (pure reshape — headers are static, never materialized on
    the wire), one all-gather stacks every child, the schedule's
    admission mask and the statically-composed net arrival order fold
    in, and the handler's slot-axis kernel aggregates the whole level.
    Bitwise identical to ``_dense_level``: same stack, same fold order,
    same kernels.
    """
    ctx = {"dtype": arena.dtype}
    stack = _all_gather_stack(plan.pack(arena), lvl.axis)      # (P, n, E)
    stack = _admit(stack, fault, fault_stats)
    order = _net_order(handler, arrival, lvl.fanin, plan.num_packets)
    if order is not None:
        stack = hd.apply_order(stack, jnp.asarray(order, jnp.int32))
    agg, _ = handler.payload_handler(stack, None, design, n_bufs, ctx)
    out = plan.unpack(handler.completion_handler(agg, ctx))
    return _mask_to_switch(out, lvl.axis, lvl.switch_rank)


def switch_allreduce_dense(arena: jax.Array, axes: Sequence[str], *,
                           reproducible: bool = False,
                           design: str = "auto",
                           fmt: pk.PacketFormat = DEFAULT_FORMAT,
                           arrival_perms: Sequence | None = None,
                           fault_plan: pk.FaultPlan | None = None,
                           with_fault_stats: bool = False,
                           batched: bool = True,
                           mean: bool = False,
                           telemetry=None, tenant: str | None = None):
    """Allreduce a ``(B, S)`` arena through the emulated switch tree.

    ``reproducible=True`` installs the ``fixed_tree`` handler: combines
    follow the aligned binary tree over child ranks at every level, so
    the result is bitwise-invariant to packet arrival order *and*
    bitwise-equal to the wire ``fixed_tree`` collective
    (``collectives.allreduce`` with ``algorithm="fixed_tree"``) — the
    same combine tree, executed in-switch instead of rank-to-rank.

    ``fault_plan`` replays a deterministic lossy fabric on every up-hop
    (DESIGN.md §14): the reliability layer recovers the clean child
    stack exactly once per packet, so a surviving plan leaves the result
    bitwise identical to the fault-free run.  ``with_fault_stats``
    additionally returns the traced retry/rejection counters.

    ``batched=True`` (the default) runs each level as a few batched
    operations over the packed slot tensor; ``batched=False`` keeps the
    per-slot/per-hop schedule as the bitwise oracle (the two paths are
    cross-checked bit for bit in the multidevice ``switch`` group).
    """
    b, s = arena.shape
    handler = hd.get_handler("fixed_tree" if reproducible else "dense_sum")
    design, n_bufs = resolve_design(s * arena.dtype.itemsize, design,
                                    reproducible)
    levels = _levels(axes)
    fstats = _new_fault_stats()
    if len(levels) == 1 and levels[0].fanin == 1:
        return (arena, fstats) if with_fault_stats else arena
    faults = fault_schedules(fault_plan, level_packet_counts(
        [l.fanin for l in levels], b, s, arena.dtype, mode="dense", fmt=fmt))
    obs = _PlaneObs(telemetry, tenant)
    obs.retries(faults)
    cur = arena
    if batched:
        plan = pk.FramePlan(b, s, arena.dtype, fmt)
        for i, lvl in enumerate(levels):
            arrival = arrival_perms[i] if arrival_perms is not None else None
            with obs(f"plane.l{i + 1}", mode="dense", fanin=lvl.fanin):
                cur = _dense_level_batched(cur, lvl, handler, design, n_bufs,
                                           plan, arrival, fault=faults[i],
                                           fault_stats=fstats)
        with obs("plane.multicast", mode="dense"):
            cur = _multicast_root(cur, levels)
    else:
        for i, lvl in enumerate(levels):
            arrival = arrival_perms[i] if arrival_perms is not None else None
            with obs(f"plane.l{i + 1}", mode="dense", fanin=lvl.fanin):
                cur = _dense_level(cur, lvl, handler, design, n_bufs, fmt,
                                   arrival, fault=faults[i],
                                   fault_stats=fstats)
        with obs("plane.multicast", mode="dense"):
            for lvl in reversed(levels):
                cur = _multicast_arena(cur, lvl, fmt)
    if mean:
        cur = cur / lax.axis_size(tuple(axes))
    return (cur, fstats) if with_fault_stats else cur


# ---------------------------------------------------------------------------
# int8 dequant-accumulate data plane (F1).
# ---------------------------------------------------------------------------

def _scales_format(fmt: pk.PacketFormat, block: int) -> pk.PacketFormat:
    """The fp32 scales sideband: one packet per payload packet.

    Requires the payload MTU to hold whole quantization blocks — that
    is what keeps the sideband's packet count aligned with the
    payload's (``E_s = E / block``) through any tail padding.
    """
    e = fmt.payload_elems(jnp.int8)
    if e % block:
        raise ValueError(
            f"int8 switch transport needs the packet MTU ({fmt.mtu_bytes} B) "
            f"to hold whole quantization blocks of {block}")
    return pk.PacketFormat(mtu_bytes=e // block * 4)


def switch_allreduce_int8(arena: jax.Array, axes: Sequence[str], *,
                          block: int = 256,
                          design: str = "auto",
                          fmt: pk.PacketFormat = DEFAULT_FORMAT,
                          arrival_perms: Sequence | None = None,
                          fault_plan: pk.FaultPlan | None = None,
                          with_fault_stats: bool = False,
                          batched: bool = True,
                          mean: bool = False,
                          telemetry=None, tenant: str | None = None):
    """int8-transport allreduce through the emulated switch.

    Packets carry int8 payloads with a per-``block`` fp32 scale
    sideband; every switch runs the ``int8_dequant`` handler (fused
    dequantize-accumulate into an fp32 buffer — the "FPU in every HPU")
    and requantizes the aggregate for the next wire hop; the root
    requantizes once for the multicast down.  Quantization error is one
    round per tree level up plus one down, the in-network analogue of
    ``compression.quantized_allreduce``'s transport-precision trade.
    """
    b, s0 = arena.shape
    handler = hd.get_handler("int8_dequant")
    sfmt = _scales_format(fmt, block)
    levels = _levels(axes)
    fstats = _new_fault_stats()
    if len(levels) == 1 and levels[0].fanin == 1:
        return (arena, fstats) if with_fault_stats else arena
    # quantization needs whole blocks; packet alignment needs nothing
    # extra — the scales sideband's packet count matches the payload's
    # by construction (E_s = E/block), padding included
    pad = (-s0) % block
    xp = jnp.concatenate(
        [arena, jnp.zeros((b, pad), arena.dtype)], axis=1) if pad else arena
    s = xp.shape[1]
    design, n_bufs = resolve_design(s, design)     # int8: S bytes per block
    faults = fault_schedules(fault_plan, level_packet_counts(
        [l.fanin for l in levels], b, s0, arena.dtype, mode="int8", fmt=fmt,
        block=block))
    obs = _PlaneObs(telemetry, tenant)
    obs.retries(faults)

    acc = xp.astype(jnp.float32)
    e = fmt.payload_elems(jnp.int8)
    npkt = fmt.packets_per_block(s, jnp.int8)
    qplan = pk.FramePlan(b, s, jnp.int8, fmt)
    splan = pk.FramePlan(b, s // block, jnp.float32, sfmt)
    for i, lvl in enumerate(levels):
        with obs(f"plane.l{i + 1}", mode="int8", fanin=lvl.fanin):
            q, scales = compression.quantize_int8(acc, block)
            if batched:
                # two collectives per level (payload + scales sideband);
                # the int8 handler is child-steered, so any arrival
                # interleave composes with its steering to the identity
                # (_net_order) and is never materialized
                qs = _all_gather_stack(qplan.pack(q), lvl.axis)
                ss = _all_gather_stack(splan.pack(scales), lvl.axis)
                # "q" is the admission-gated stream; the scales sideband
                # fate-shares the delivered mask
                payload = _admit({"q": qs, "scale": ss}, faults[i], fstats)
                agg, _ = handler.payload_handler(payload, None, design,
                                                 n_bufs, {"qblock": block})
                acc = qplan.unpack(agg)                    # (B, S) fp32
                acc = _mask_to_switch(acc, lvl.axis, lvl.switch_rank)
                continue
            r = lax.axis_index(lvl.axis)
            streams = {"q": pk.packetize(q, fmt, child_rank=r),
                       "scale": pk.packetize(scales, sfmt, child_rank=r)}
            stacked = _gather_children(streams, lvl.axis)
            payload = {"q": stacked["q"].payload,
                       "scale": stacked["scale"].payload}
            headers = stacked["q"].headers
            if faults[i] is not None:
                # "q" is the checksummed stream (its headers steer the
                # stack); the scales sideband fate-shares the accept mask
                payload, headers = _reliable_ingress(payload, headers,
                                                     faults[i], fstats)
            arrival = (arrival_perms[i] if arrival_perms is not None
                       else None)
            payload, headers = _apply_arrival(payload, headers, arrival)
            agg, _ = hd.run(handler, payload, headers, design=design,
                            n_bufs=n_bufs, ctx={"qblock": block})
            acc = agg.reshape(b, npkt * e)[:, :s]          # (n, E) fp32
            acc = _mask_to_switch(acc, lvl.axis, lvl.switch_rank)

    # root multicast: requantize once, stream int8 + scales back down
    with obs("plane.multicast", mode="int8"):
        q, scales = compression.quantize_int8(acc, block)
        if batched:
            q, scales = _multicast_root((q, scales), levels)
        else:
            streams = {"q": pk.packetize(q, fmt),
                       "scale": pk.packetize(scales, sfmt)}
            for lvl in reversed(levels):
                streams = _multicast(streams, lvl.axis, lvl.switch_rank)
            q = pk.depacketize(streams["q"], fmt, b, s)
            scales = pk.depacketize(streams["scale"], sfmt, b, s // block)
    out = compression.dequantize_int8(q, scales, block, dtype=arena.dtype)
    out = out[:, :s0]
    if mean:
        out = out / lax.axis_size(tuple(axes))
    return (out, fstats) if with_fault_stats else out


# ---------------------------------------------------------------------------
# Sparse coordinate-merge data plane (§7).
# ---------------------------------------------------------------------------

def _pack_lists(idx: jax.Array, val32: jax.Array) -> jax.Array:
    """(B, cap) idx + fp32 val → (B, 2·cap) int32 wire image (bit-exact)."""
    return jnp.concatenate(
        [idx, lax.bitcast_convert_type(val32, jnp.int32)], axis=1)


def _unpack_lists(packed: jax.Array, cap: int) -> tuple[jax.Array, jax.Array]:
    return (packed[..., :cap],
            lax.bitcast_convert_type(packed[..., cap:], jnp.float32))


def _densify(idx: jax.Array, val32: jax.Array, b: int, s: int) -> jax.Array:
    """§7 array storage: scatter-add ``(B, cap)`` lists into a dense
    ``(B, S)`` fp32 buffer — the slot-axis ``kernels/sparse_accum_slots``
    Pallas kernel, one grid over every bucket (sentinels → -1)."""
    lidx = jnp.where(idx != sparse.SENTINEL, idx, -1)
    return ops.sparse_accum_slots(lidx, val32, s)


def switch_allreduce_sparse(arena: jax.Array, axes: Sequence[str],
                            ks: Sequence[int] | int, *,
                            density_threshold: float = 0.25,
                            fmt: pk.PacketFormat = DEFAULT_FORMAT,
                            arrival_perms: Sequence | None = None,
                            fault_plan: pk.FaultPlan | None = None,
                            with_fault_stats: bool = False,
                            batched: bool = True,
                            mean: bool = False,
                            with_stats: bool = False,
                            telemetry=None, tenant: str | None = None):
    """Top-k sparse allreduce through the emulated switch (§7).

    Hosts send their top-k coordinate lists as (idx, val) packets; each
    switch runs the ``sparse_merge`` handler (sorted-list
    insert-or-accumulate, collisions counted), forwarding the merged
    list — capacity ``k · fanin`` — up the tree while it fits under
    ``density_threshold · S``, densifying at whichever level it stops
    fitting (the paper's hash-at-the-leaves / array-at-the-root split).
    The final dense accumulate is the ``kernels/sparse_accum`` Pallas
    kernel — literally the paper's array storage — and the root
    multicasts the dense result down.

    Returns ``(reduced, mine)`` like ``sparse.sparse_allreduce`` (and
    ``stats`` — traced collision/spill counters on this rank's
    root-path switches — when ``with_stats``).
    """
    b, s = arena.shape
    handler = hd.get_handler("sparse_merge")
    ks = tuple(int(k) for k in (ks if hasattr(ks, "__len__") else [ks] * b))
    if len(ks) != b:
        raise ValueError(f"got {len(ks)} ks for {b} buckets")
    k_max = max(ks)
    ks_arr = jnp.asarray(ks, jnp.int32)
    levels = _levels(axes)

    val, idx = jax.vmap(
        lambda v, ke: sparse.topk_sparsify(v, k_max, ke))(arena, ks_arr)
    mine = jax.vmap(
        lambda v, i: sparse.scatter_dense(v, i, s, dtype=arena.dtype))(val,
                                                                       idx)
    fstats = _new_fault_stats()
    if len(levels) == 1 and levels[0].fanin == 1:
        out = mine.astype(jnp.float32)
        if mean:
            out = out / lax.axis_size(tuple(axes))
        ret = [out.astype(arena.dtype), mine]
        if with_stats:
            ret.append({"collisions": jnp.zeros((), jnp.int32),
                        "spill_bytes": jnp.zeros((), jnp.int32)})
        if with_fault_stats:
            ret.append(fstats)
        return tuple(ret)
    val32 = val.astype(jnp.float32)
    cap = k_max
    dense_acc: jax.Array | None = None
    collisions = jnp.zeros((), jnp.int32)
    faults = fault_schedules(fault_plan, level_packet_counts(
        [l.fanin for l in levels], b, s, arena.dtype, mode="sparse", fmt=fmt,
        k_max=k_max, density_threshold=density_threshold))
    obs = _PlaneObs(telemetry, tenant)
    obs.retries(faults)

    dplan = pk.FramePlan(b, s, jnp.float32, fmt)
    for i, lvl in enumerate(levels):
        with obs(f"plane.l{i + 1}", mode="sparse", fanin=lvl.fanin):
            arrival = arrival_perms[i] if arrival_perms is not None else None
            if dense_acc is None and sparse.densify_step(
                    cap * lvl.fanin, s, density_threshold):
                # array storage from here on: this level would overflow the
                # list capacity, so densify before the hop (§7 densification
                # toward the root)
                dense_acc = _densify(idx, val32, b, s)
            if dense_acc is not None:
                # child-steered dense sum: the fold order stays a pure
                # function of child rank, so the sparse plane is bitwise
                # arrival-invariant even after it densifies mid-tree
                if batched:
                    dense_acc = _dense_level_batched(
                        dense_acc, lvl, hd.get_handler("dense_sum_steered"),
                        "single", 1, dplan, arrival,
                        fault=faults[i], fault_stats=fstats)
                else:
                    dense_acc = _dense_level(dense_acc, lvl,
                                             hd.get_handler("dense_sum_steered"),
                                             "single", 1, fmt, arrival,
                                             fault=faults[i], fault_stats=fstats)
                continue
            packed = _pack_lists(idx, val32)                   # (B, 2·cap) int32
            if batched:
                # one collective gathers every child's packed wire image;
                # the merge handler regroups packets by CHILD, and arrival
                # interleave ∘ child-regroup is the identity on each child's
                # image, so reassembly is a pure unframe (reshape + slice)
                lplan = pk.FramePlan(b, 2 * cap, jnp.int32, fmt)
                stack = _all_gather_stack(lplan.pack(packed), lvl.axis)
                stack = _admit(stack, faults[i], fstats)
                child_packed = lplan.unpack(stack)             # (P, B, 2·cap)
                cidx, cval = _unpack_lists(child_packed, cap)  # (P, B, cap)
                merged, stats = handler.payload_handler(
                    {"idx": cidx, "val": cval}, None, "single", 1, {})
            else:
                r = lax.axis_index(lvl.axis)
                stream = pk.packetize(packed, fmt, child_rank=r)
                stacked = _gather_children(stream, lvl.axis)
                payload, headers = stacked.payload, stacked.headers
                if faults[i] is not None:
                    payload, headers = _reliable_ingress(payload, headers,
                                                         faults[i], fstats)
                payload, headers = _apply_arrival(payload, headers, arrival)
                # a coordinate list spans several packets, so the reassembly
                # of each child's wire image must group packets by the CHILD
                # header, not by arrival position — under a per-slot arrival
                # interleave the stack rows mix children, and pairing child
                # A's indices with child B's values would silently corrupt
                # the sum
                order = hd.child_order(headers)
                payload = hd.apply_order(payload, order)
                headers = hd.apply_order(headers, order)
                # reassemble each child's wire image from its packets, merge
                child_packed = jax.vmap(
                    lambda pl, hdrs: pk.depacketize(pk.PacketStream(hdrs, pl),
                                                    fmt, b, 2 * cap)
                )(payload, headers)
                cidx, cval = _unpack_lists(child_packed, cap)  # (P, B, cap)
                merged, stats = hd.run(handler, {"idx": cidx, "val": cval},
                                       headers, design="single")
            collisions = collisions + stats["collisions"]
            cap *= lvl.fanin
            idx, val32 = merged["idx"], merged["val"]
            r_sw = lax.axis_index(lvl.axis)
            idx = jnp.where(r_sw == lvl.switch_rank, idx,
                            jnp.full_like(idx, sparse.SENTINEL))
            val32 = jnp.where(r_sw == lvl.switch_rank, val32,
                              jnp.zeros_like(val32))

    if dense_acc is None:
        # root array storage (§7)
        dense_acc = _densify(idx, val32, b, s)
        dense_acc = _mask_to_switch(dense_acc, levels[-1].axis,
                                    levels[-1].switch_rank)

    with obs("plane.multicast", mode="sparse"):
        if batched:
            dense_acc = _multicast_root(dense_acc, levels)
        else:
            for lvl in reversed(levels):
                dense_acc = _multicast_arena(dense_acc, lvl, fmt)
    if mean:
        dense_acc = dense_acc / lax.axis_size(tuple(axes))
    red = dense_acc.astype(arena.dtype)
    ret = [red, mine]
    if with_stats:
        ret.append({"collisions": collisions,
                    "spill_bytes": collisions * 2 * 4})  # (idx, val)/spill
    if with_fault_stats:
        ret.append(fstats)
    return tuple(ret)


# ---------------------------------------------------------------------------
# Static packet/combine counters — the perfmodel cross-check surface.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LevelCounters:
    """Per-switch traffic and work at one tree level, per allreduce."""

    axis: str
    fanin: int                  # P: packets per block arriving at a switch
    ingress_packets: int        # blocks · fanin received per switch
    egress_packets: int         # blocks forwarded up (1 per block)
    combines: int               # blocks · (fanin − 1) combine ops
    buffers_per_block: float    # M — the working-memory multiplier


@dataclasses.dataclass(frozen=True)
class SwitchCounters:
    """What the data plane will execute for one ``(B, S)`` arena.

    These are exactly the analytic model's inputs: ``payload_elems`` is
    the paper's ``N``, each level's ``fanin`` its ``P``, ``combines``
    the ``P−1``-per-block count every §6 service time amortizes, and
    ``buffers_per_block`` the ``M`` of the working-memory equation
    (Little's law, §4.3).  ``tests/test_switch.py`` feeds them back
    into ``perfmodel.switch_model`` to pin the two layers together.
    """

    levels: tuple[LevelCounters, ...]
    blocks: int                 # B · ceil(S/N) reduction blocks framed
    payload_elems: int          # N
    packet_bytes: int           # MTU
    design: str
    n_bufs: int

    @property
    def total_combines(self) -> int:
        return sum(l.combines for l in self.levels)

    def model_point(self, data_bytes: int) -> "sm.DesignPoint":
        """Evaluate the analytic model at this plane's operating point."""
        params = sm.SwitchParams(packet_bytes=self.packet_bytes)
        return sm.model_design(self.design, data_bytes, params,
                               B=self.n_bufs, P=self.levels[0].fanin)


def _counters(level_fanins: Sequence[tuple[str, int]], num_buckets: int,
              bucket_elems: int, dtype, fmt: pk.PacketFormat,
              design: str, reproducible: bool) -> SwitchCounters:
    """Shared counter math for a sequence of (axis label, fan-in) levels."""
    n = fmt.payload_elems(dtype)
    npkt = fmt.packets_per_block(bucket_elems, dtype)
    blocks = num_buckets * npkt
    nbytes = bucket_elems * jnp.dtype(dtype).itemsize
    design, n_bufs = resolve_design(nbytes, design, reproducible)
    levels = []
    for axis, p in level_fanins:
        levels.append(LevelCounters(
            axis=axis, fanin=p,
            ingress_packets=blocks * p,
            egress_packets=blocks,
            combines=blocks * hd.combines_per_packet_slot(p, design),
            buffers_per_block=sm.buffers_per_block(design, p, n_bufs)))
    return SwitchCounters(levels=tuple(levels), blocks=blocks,
                          payload_elems=n, packet_bytes=fmt.mtu_bytes,
                          design=design, n_bufs=n_bufs)


def plan_counters(axis_names: Sequence[str], axis_sizes: Sequence[int],
                  num_buckets: int, bucket_elems: int, dtype, *,
                  fmt: pk.PacketFormat = DEFAULT_FORMAT,
                  design: str = "auto",
                  reproducible: bool = False,
                  batched: bool = True) -> SwitchCounters:
    """Static counters for the plane's schedule on a mesh (no tracing).

    ``batched`` is accepted (and ignored) so callers can pass the
    transport's knob straight through: batching changes the *schedule*
    of the emulation, never the modeled switch work — the same packets
    arrive, the same combines run, the same buffers hold them — so the
    counters are identical for both paths (pinned in
    ``tests/test_switch.py``).
    """
    del batched
    fanins = [(lvl.axis, lvl.fanin) for lvl in
              topology.mesh_levels(tuple(axis_names), tuple(axis_sizes))]
    return _counters(fanins, num_buckets, bucket_elems, dtype, fmt,
                     design, reproducible)


def tree_counters(tree: topology.ReductionTree, num_buckets: int,
                  bucket_elems: int, dtype, *,
                  fmt: pk.PacketFormat = DEFAULT_FORMAT,
                  design: str = "auto",
                  reproducible: bool = False,
                  batched: bool = True) -> SwitchCounters:
    """Static counters for an arbitrary :class:`topology.ReductionTree`.

    ``plan_counters`` reads fan-ins off the mesh axes; this variant reads
    them off the tree itself — the multi-tenant runtime's path after a
    switch failure, where ``rebuild_excluding_switch`` grows fan-ins past
    the axis sizes and the rebuilt tree (not the mesh) is the source of
    truth for admission and scheduling.  Per level the fan-in is the
    *largest* child count at that level (the busiest switch bounds the
    schedule); a single-host tree degenerates to one fan-in-1 level,
    matching ``topology.mesh_levels``.  ``batched`` is ignored exactly
    as in :func:`plan_counters`.
    """
    del batched
    fanins = [(f"level{lvl}",
               max(len(tree.nodes[i].children) for i in tree.levels[lvl]))
              for lvl in range(1, len(tree.levels))]
    if not fanins:
        fanins = [("level1", 1)]
    return _counters(fanins, num_buckets, bucket_elems, dtype, fmt,
                     design, reproducible)
