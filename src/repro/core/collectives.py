"""Flare allreduce algorithms as explicit JAX mesh collectives.

Every function in this module executes *inside* a ``jax.shard_map`` manual
region (the reduction axes — usually ``data`` and ``pod`` — are manual;
the ``model`` axis stays auto/XLA).  Each algorithm is the TPU-native
analogue of one of the paper's switch aggregation designs (§6):

=====================  =====================================================
paper design            TPU analogue (this module)
=====================  =====================================================
host-based ring [8]     ``allreduce_ring`` — Rabenseifner reduce-scatter +
                        all-gather over ``lax.ppermute`` rings.  The paper's
                        *baseline*; ~2Z bytes sent per rank.
tree aggregation §6.3   ``allreduce_fixed_tree`` — recursive doubling over a
                        rank-indexed aligned binary tree; contention-free,
                        latency-optimal (log P steps), combine order a pure
                        function of rank ids → bitwise-reproducible (F3).
multi-buffer §6.2       ``allreduce_rhd`` — recursive halving-doubling:
                        log P steps like the tree, but vector-halving keeps
                        wire bytes at ~2Z(P-1)/P (bandwidth-optimal); the
                        B-buffer parallelism maps to the per-segment
                        independence of the halved exchanges.
in-network tree §1,§4   ``allreduce_two_level`` — reduce-scatter on the
                        intra-pod axis (leaf switch aggregates its children),
                        allreduce across pods (root of the reduction tree),
                        all-gather back down (root multicast).  Each rank
                        puts ~Z bytes on the intra-pod wire: the paper's
                        2x traffic reduction over the ring.
SHARP/fixed-function    ``allreduce_psum`` — ``jax.lax.psum``: the opaque
                        vendor collective (fast, non-customizable,
                        unspecified reduction order).
=====================  =====================================================

All algorithms are parametric in the element dtype and in the combine
operator (F1): any associative jnp binop for the non-reproducible paths, a
fixed-order sum for the reproducible path.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import topology

Op = Callable[[jax.Array, jax.Array], jax.Array]


def _is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


def _ring_perm(p: int) -> list[tuple[int, int]]:
    return [(i, (i + 1) % p) for i in range(p)]


def xor_perm(p: int, d: int) -> list[tuple[int, int]]:
    """The recursive-doubling involution at distance ``d``: rank i <-> i^d.

    One XOR step of every log-depth schedule in this repo — rhd,
    fixed-tree, and the sparse coordinate-list exchange all walk
    ``xor_perm(p, 1<<s)`` for s in range(log2 P).
    """
    return [(i, i ^ d) for i in range(p)]


def _bitrev_perm(p: int) -> list[tuple[int, int]]:
    """The bit-reversal involution: rank i <-> bitrev(i).

    ``rhd_reduce_scatter`` leaves rank ``r`` holding segment ``bitrev(r)``;
    one ppermute along this involution restores standard (rank r ↔ segment
    r) placement, which the FSDP layout requires.
    """
    bits = p.bit_length() - 1
    def rev(i: int) -> int:
        out = 0
        for b in range(bits):
            out |= ((i >> b) & 1) << (bits - 1 - b)
        return out
    return [(i, rev(i)) for i in range(p)]


def pad_to_multiple(x: jax.Array, m: int) -> tuple[jax.Array, int]:
    """Pad leading axis of ``x`` to a multiple of ``m``; return (padded, n)."""
    n = x.shape[0]
    rem = (-n) % m
    if rem:
        x = jnp.concatenate([x, jnp.zeros((rem,) + x.shape[1:], x.dtype)])
    return x, n


# ---------------------------------------------------------------------------
# Ring (Rabenseifner) — the paper's host-based baseline.
# ---------------------------------------------------------------------------

@jax.named_scope("flare.ring.reduce_scatter")
def ring_reduce_scatter(x: jax.Array, axis: str, *, op: Op = jnp.add,
                        stagger: int = 0) -> jax.Array:
    """Reduce-scatter a flat vector over ``axis`` with a ppermute ring.

    Rank ``r`` returns the fully reduced chunk ``(r + 1 + stagger) % P``.
    ``stagger`` rotates which chunk each rank starts from — the paper's
    *staggered sending* (§5): concurrent buckets use different offsets so
    their traffic never contends for the same chunk/link at the same step.
    ``x.shape[0]`` must be divisible by the axis size.
    """
    p = lax.axis_size(axis)
    r = lax.axis_index(axis)
    if x.shape[0] % p:
        raise ValueError(f"ring_reduce_scatter: len {x.shape[0]} % {p} != 0")
    chunks = x.reshape((p, x.shape[0] // p) + x.shape[1:])
    perm = _ring_perm(p)
    send0 = jnp.take(chunks, (r + stagger) % p, axis=0)

    def body(s, carry):
        chunks, acc = carry
        recv = lax.ppermute(acc, axis, perm)
        mine = jnp.take(chunks, (r - s - 1 + stagger) % p, axis=0)
        return chunks, op(mine, recv)

    _, acc = lax.fori_loop(0, p - 1, body, (chunks, send0))
    return acc


@jax.named_scope("flare.ring.all_gather")
def ring_all_gather(chunk: jax.Array, axis: str, *, stagger: int = 0) -> jax.Array:
    """Inverse of ``ring_reduce_scatter``: gather P chunks back to a vector."""
    p = lax.axis_size(axis)
    r = lax.axis_index(axis)
    perm = _ring_perm(p)
    out0 = jnp.zeros((p,) + chunk.shape, chunk.dtype)
    out0 = lax.dynamic_update_index_in_dim(out0, chunk, (r + 1 + stagger) % p, 0)

    def body(s, carry):
        out, send = carry
        recv = lax.ppermute(send, axis, perm)
        out = lax.dynamic_update_index_in_dim(out, recv, (r - s + stagger) % p, 0)
        return out, recv

    out, _ = lax.fori_loop(0, p - 1, body, (out0, chunk))
    return out.reshape((p * chunk.shape[0],) + chunk.shape[1:])


def allreduce_ring(x: jax.Array, axis: str, *, op: Op = jnp.add,
                   stagger: int = 0) -> jax.Array:
    """Rabenseifner ring allreduce: ~2Z(P-1)/P bytes per rank on the wire."""
    p = lax.axis_size(axis)
    xp, n = pad_to_multiple(x, p)
    chunk = ring_reduce_scatter(xp, axis, op=op, stagger=stagger)
    full = ring_all_gather(chunk, axis, stagger=stagger)
    return full[:n]


# ---------------------------------------------------------------------------
# Pipelined ring — B blocks in flight via the batched arena schedule (§6.2).
# ---------------------------------------------------------------------------
#
# The paper's multi-buffer aggregation keeps B reduction blocks in flight:
# while block b's reduced chunks travel back down (all-gather), block b+1's
# chunks are still being combined on the way up (reduce-scatter).  Our
# realization is ``ring_allreduce_bucketed`` — B arbitrary blocks at once
# via the vmapped ring: every round batches all B blocks' chunks into ONE
# ppermute, 2(P-1) collective rounds total instead of the 2B(P-1) a
# per-bucket loop costs.  (A single-vector double-buffer form with fused
# all-gather/reduce-scatter waves — ``allreduce_ring_pipelined`` — was
# retired: its fused sends measured *slower* than the plain ring it
# pipelined, 462ms vs 281ms at 16 MiB, because a fori_loop stacking two
# chunks per ppermute serializes exactly like two rings on the emulated
# fabric; the arena schedule is the form that actually overlaps.)


def ring_allreduce_bucketed(arena: jax.Array, axis: str, *, op: Op = jnp.add,
                            staggers: jax.Array | None = None) -> jax.Array:
    """Ring allreduce of B equal-size buckets with all B blocks in flight.

    ``arena`` is ``(B, S)`` with ``S`` divisible by the axis size (the
    arena plan guarantees this).  The schedule is the vmapped ring: round
    s of *every* bucket's reduce-scatter (then all-gather) executes as
    ONE batched ppermute carrying a ``(B, S/P)`` payload — the paper's B
    concurrent reduction blocks sharing the network (§6.2), each offset
    by its own ``stagger`` phase (§5) so no two blocks touch the same
    chunk index in the same round.  2(P-1) collective rounds total,
    versus 2B(P-1) for the seed's one-bucket-at-a-time loop; per bucket
    the combine chain is exactly ``allreduce_ring``'s, so results are
    bitwise-equal to the per-bucket loop.
    """
    b, size = arena.shape
    p = lax.axis_size(axis)
    if p == 1:
        return arena
    if size % p:
        raise ValueError(f"ring_allreduce_bucketed: S {size} % {p} != 0")
    if staggers is None:
        staggers = jnp.zeros((b,), jnp.int32)
    return jax.vmap(
        lambda v, s: allreduce_ring(v, axis, op=op, stagger=s)
    )(arena, staggers)


# ---------------------------------------------------------------------------
# Recursive halving-doubling — bandwidth-optimal, log P steps.
# ---------------------------------------------------------------------------

@jax.named_scope("flare.rhd")
def rhd_reduce_scatter(x: jax.Array, axis: str, *, op: Op = jnp.add) -> jax.Array:
    """Vector-halving distance-doubling reduce-scatter (power-of-two P).

    The combine tree per final segment is the *aligned binary tree over
    rank ids* — fixed by construction, independent of arrival order, so
    this path is also bitwise-reproducible for commutative IEEE ops
    (addition is commutative bitwise; only associativity is not).
    Rank ``r`` ends with the segment at bit-reversed position; use
    ``rhd_all_gather`` to invert.
    """
    p = lax.axis_size(axis)
    if not _is_pow2(p):
        raise ValueError(f"rhd requires power-of-two axis size, got {p}")
    r = lax.axis_index(axis)
    if x.shape[0] % p:
        raise ValueError(f"rhd_reduce_scatter: len {x.shape[0]} % {p} != 0")
    steps = p.bit_length() - 1
    for k in range(steps):
        d = 1 << k
        perm = xor_perm(p, d)
        half = x.shape[0] // 2
        lo, hi = x[:half], x[half:]
        bit = jnp.reshape((r & d) != 0, (1,) * x.ndim)
        send = jnp.where(bit, lo, hi)        # keep hi if my bit is set
        recv = lax.ppermute(send, axis, perm)
        keep = jnp.where(bit, hi, lo)
        x = op(keep, recv)
    return x


@jax.named_scope("flare.rhd")
def rhd_all_gather(seg: jax.Array, axis: str) -> jax.Array:
    """Distance-halving all-gather inverting ``rhd_reduce_scatter``."""
    p = lax.axis_size(axis)
    r = lax.axis_index(axis)
    steps = p.bit_length() - 1
    for k in reversed(range(steps)):
        d = 1 << k
        perm = xor_perm(p, d)
        recv = lax.ppermute(seg, axis, perm)
        bit = jnp.reshape((r & d) != 0, (1,) * seg.ndim)
        seg = jnp.where(bit,
                        jnp.concatenate([recv, seg]),
                        jnp.concatenate([seg, recv]))
    return seg


def allreduce_rhd(x: jax.Array, axis: str, *, op: Op = jnp.add) -> jax.Array:
    """Recursive halving-doubling allreduce (multi-buffer design analogue)."""
    p = lax.axis_size(axis)
    xp, n = pad_to_multiple(x, p)
    seg = rhd_reduce_scatter(xp, axis, op=op)
    full = rhd_all_gather(seg, axis)
    return full[:n]


# ---------------------------------------------------------------------------
# Fixed-tree (tree aggregation §6.3) — reproducible, latency-optimal.
# ---------------------------------------------------------------------------

@jax.named_scope("flare.fixed_tree")
def allreduce_fixed_tree(x: jax.Array, axis: str, *, op: Op = jnp.add,
                         accum_dtype: jnp.dtype | None = None) -> jax.Array:
    """Recursive-doubling allreduce over a fixed aligned binary tree.

    At step k each rank combines with rank ``r ^ 2^k``; the combine tree is
    ``((0,1),(2,3)),((4,5),(6,7)) ...`` — a pure function of rank ids,
    never of arrival order.  With ``accum_dtype=float32`` this is the
    paper's reproducible mode (F3): bitwise-identical across runs and
    allocations.  Wire bytes: Z log2(P) per rank (latency-optimal; the
    paper pays the same structural price — tree aggregation keeps
    (P-1)/log(P) buffers alive instead of 1).
    """
    p = lax.axis_size(axis)
    if not _is_pow2(p):
        raise ValueError(f"fixed_tree requires power-of-two axis size, got {p}")
    orig_dtype = x.dtype
    if accum_dtype is not None:
        x = x.astype(accum_dtype)
    steps = p.bit_length() - 1
    for k in range(steps):
        d = 1 << k
        perm = xor_perm(p, d)
        recv = lax.ppermute(x, axis, perm)
        # IEEE addition is commutative bitwise, so op(x, recv) on one side
        # and op(recv, x) on the other produce identical bits; the tree
        # *shape* (which partials meet) is fixed by the XOR schedule.
        x = op(x, recv)
    return x.astype(orig_dtype)


# ---------------------------------------------------------------------------
# Two-level hierarchical — the in-network reduction tree (§1, §4).
# ---------------------------------------------------------------------------

@jax.named_scope("flare.two_level")
def allreduce_two_level(x: jax.Array, inner_axis: str, outer_axis: str, *,
                        op: Op = jnp.add,
                        inner: str = "ring",
                        outer: str = "rhd",
                        stagger: int = 0) -> jax.Array:
    """Hierarchical allreduce = the paper's in-network reduction tree.

    Phase 1 (leaf switch): reduce-scatter over ``inner_axis`` — the
      intra-pod chips aggregate their children's data; each rank now owns
      1/P_in of the partially-reduced vector (the "aggregation buffer").
    Phase 2 (root switch): allreduce the owned segment over ``outer_axis``
      — the tree's upper level combines per-pod partials.
    Phase 3 (root multicast): all-gather over ``inner_axis`` sends the
      fully-reduced data back down the tree.

    Wire traffic per rank: ~Z on the inner axis (vs ~2Z for a flat ring
    over all P ranks — the paper's 2x in-network traffic reduction shows up
    exactly here) plus Z/P_in * f(P_out) on the scarce inter-pod links.
    """
    p_in = lax.axis_size(inner_axis)
    xp, n = pad_to_multiple(x, p_in)
    if inner == "ring":
        seg = ring_reduce_scatter(xp, inner_axis, op=op, stagger=stagger)
    elif inner == "rhd":
        seg = rhd_reduce_scatter(xp, inner_axis, op=op)
    else:
        raise ValueError(f"unknown inner algorithm {inner!r}")

    if outer == "rhd":
        seg = allreduce_rhd(seg, outer_axis, op=op)
    elif outer == "ring":
        seg = allreduce_ring(seg, outer_axis, op=op, stagger=stagger)
    elif outer == "fixed_tree":
        seg = allreduce_fixed_tree(seg, outer_axis, op=op)
    elif outer == "psum":
        seg = lax.psum(seg, outer_axis)
    else:
        raise ValueError(f"unknown outer algorithm {outer!r}")

    if inner == "ring":
        full = ring_all_gather(seg, inner_axis, stagger=stagger)
    else:
        full = rhd_all_gather(seg, inner_axis)
    return full[:n]


# ---------------------------------------------------------------------------
# Tree-driven hierarchical schedule — the ReductionTree as source of truth.
# ---------------------------------------------------------------------------

@jax.named_scope("flare.hierarchical")
def hierarchical_allreduce(x: jax.Array, axes: tuple[str, ...], *,
                           op: Op = jnp.add,
                           stagger: int = 0,
                           fixed_tree: bool = False,
                           accum_dtype: jnp.dtype | None = None) -> jax.Array:
    """Allreduce scheduled by the mesh's reduction tree (§1, §4).

    ``axes`` is outermost-first (``("pod", "data")``).  The schedule
    walks ``topology.mesh_levels``: level 1 (leaf switches) reduce-
    scatters over the innermost axis — each rank ends owning
    ``1/fanin`` of the partially-reduced vector, the leaf switch's
    aggregation buffer — levels ≥ 2 allreduce the owned segment over
    their axes (the tree's upper switches), and the root multicast is
    the closing all-gather back over level 1.  Inter-level traffic per
    rank is ``~Z/leaf_fanin · f(outer)`` instead of the flat schedule's
    ``~Z`` — the switch-aggregation bandwidth argument on mesh wires.

    ``fixed_tree=True`` is the reproducible variant (F3): the leaf level
    runs the recursive-halving reduce-scatter (per-segment combine tree
    = the aligned binary tree over inner rank ids), upper levels the
    XOR fixed tree, with fp32 accumulation.  Every combine is a pure
    function of rank ids, never of arrival order or device placement —
    bitwise-identical across runs and device permutations.  Requires
    power-of-two axis sizes.

    Per-level wire algorithms otherwise come from the level fan-in:
    power-of-two fan-ins take the log-depth rhd path, others the ring.
    """
    sizes = tuple(lax.axis_size(a) for a in axes)
    levels = topology.mesh_levels(axes, sizes)
    if len(levels) == 1 and levels[0].fanin == 1:       # 1-host mesh
        return x
    leaf = levels[0]

    orig_dtype = x.dtype
    if fixed_tree:
        if accum_dtype is None:
            accum_dtype = jnp.float32
        if any(not _is_pow2(l.fanin) for l in levels):
            raise ValueError(
                f"hierarchical fixed_tree requires power-of-two fan-ins, "
                f"got {[l.fanin for l in levels]}")
        x = x.astype(accum_dtype)

    xp, n = pad_to_multiple(x, leaf.fanin)
    # level 1: leaf-switch aggregation (reduce-scatter over the inner axis)
    if fixed_tree or _is_pow2(leaf.fanin):
        seg = rhd_reduce_scatter(xp, leaf.axis, op=op)
    else:
        seg = ring_reduce_scatter(xp, leaf.axis, op=op, stagger=stagger)
    # levels >= 2: upper switches allreduce the owned segment
    for lvl in levels[1:]:
        if fixed_tree:
            seg = allreduce_fixed_tree(seg, lvl.axis, op=op)
        elif _is_pow2(lvl.fanin):
            seg = allreduce_rhd(seg, lvl.axis, op=op)
        else:
            seg = allreduce_ring(seg, lvl.axis, op=op, stagger=stagger)
    # root multicast: all-gather back down the leaf level
    if fixed_tree or _is_pow2(leaf.fanin):
        full = rhd_all_gather(seg, leaf.axis)
    else:
        full = ring_all_gather(seg, leaf.axis, stagger=stagger)
    return full[:n].astype(orig_dtype)


def hierarchical_allreduce_bucketed(arena: jax.Array, axes: tuple[str, ...],
                                    *, op: Op = jnp.add,
                                    staggers: jax.Array | None = None,
                                    fixed_tree: bool = False,
                                    accum_dtype: jnp.dtype | None = None,
                                    ) -> jax.Array:
    """Hierarchical allreduce of a ``(B, S)`` arena, all buckets in flight.

    The vmapped form of :func:`hierarchical_allreduce`: every collective
    round of every level carries all B buckets' payloads in ONE batched
    exchange (the §6.2 multi-buffer schedule applied to the tree), each
    bucket offset by its own ring ``stagger`` phase where the ring is in
    play.  Per bucket the combine chain is exactly the single-vector
    schedule's, so results are bitwise-equal to a per-bucket loop.
    """
    b = arena.shape[0]
    if staggers is None:
        staggers = jnp.zeros((b,), jnp.int32)
    return jax.vmap(
        lambda v, s: hierarchical_allreduce(v, axes, op=op, stagger=s,
                                            fixed_tree=fixed_tree,
                                            accum_dtype=accum_dtype)
    )(arena, staggers)


# ---------------------------------------------------------------------------
# Vendor baseline.
# ---------------------------------------------------------------------------

@jax.named_scope("flare.psum")
def allreduce_psum(x: jax.Array, axes: str | tuple[str, ...]) -> jax.Array:
    """XLA's native psum — the SHARP/fixed-function analogue."""
    return lax.psum(x, axes)


# ---------------------------------------------------------------------------
# Registry + dispatch (the §6.4 size-based algorithm switchover).
# ---------------------------------------------------------------------------

#: Paper §6.4: "Flare uses single buffer aggregation if the size of the data
#: to be reduced is larger than 512KiB, multi buffers ... if larger than
#: 128KiB, and tree aggregation otherwise."  Mapping onto wire algorithms:
#: tree → fixed_tree (log-depth, latency optimal), multi-buffer → rhd
#: (log-depth and bandwidth optimal), single-buffer streaming → ring
#: (pipelined streaming, bandwidth optimal, lowest working memory).
TREE_THRESHOLD = 128 << 10      # bytes
RING_THRESHOLD = 512 << 10      # bytes


def select_algorithm(nbytes: int, *, reproducible: bool = False,
                     multi_level: bool = False) -> str:
    """Size-based switchover reproducing the paper's §6.4 policy."""
    if reproducible:
        # "When reproducibility of floating-point summation is required,
        #  Flare always uses tree aggregation."
        return "fixed_tree"
    if nbytes < TREE_THRESHOLD:
        return "fixed_tree"
    if nbytes < RING_THRESHOLD:
        return "rhd"
    return "two_level" if multi_level else "ring"


def allreduce(x: jax.Array, axes: tuple[str, ...], *, algorithm: str = "auto",
              op: Op = jnp.add, reproducible: bool = False,
              stagger: int = 0,
              accum_dtype: jnp.dtype | None = None) -> jax.Array:
    """Dispatch a flat-vector allreduce over one or two mesh axes.

    ``axes`` is ``(inner,)`` or ``(outer, inner)`` (e.g. ``("pod","data")``);
    the innermost axis is the leaf-switch level of the reduction tree.
    Must be called inside a ``shard_map`` region where ``axes`` are manual.
    """
    nbytes = x.size * x.dtype.itemsize
    if algorithm == "auto":
        algorithm = select_algorithm(nbytes, reproducible=reproducible,
                                     multi_level=len(axes) > 1)
    if reproducible and algorithm not in ("fixed_tree", "hierarchical"):
        raise ValueError("reproducible mode requires the fixed_tree or "
                         "hierarchical (fixed-tree levels) algorithm")
    if accum_dtype is None and reproducible:
        accum_dtype = jnp.float32

    if algorithm == "hierarchical":
        return hierarchical_allreduce(x, axes, op=op, stagger=stagger,
                                      fixed_tree=reproducible,
                                      accum_dtype=accum_dtype)

    if len(axes) == 1:
        inner = axes[0]
        if algorithm == "ring":
            return allreduce_ring(x, inner, op=op, stagger=stagger)
        if algorithm == "rhd":
            return allreduce_rhd(x, inner, op=op)
        if algorithm == "fixed_tree":
            return allreduce_fixed_tree(x, inner, op=op, accum_dtype=accum_dtype)
        if algorithm == "psum":
            return allreduce_psum(x, inner)
        if algorithm == "two_level":
            # degenerate: no outer axis; fall back to ring
            return allreduce_ring(x, inner, op=op, stagger=stagger)
        raise ValueError(f"unknown algorithm {algorithm!r}")

    outer, inner = axes
    if algorithm == "two_level":
        return allreduce_two_level(x, inner, outer, op=op, stagger=stagger)
    if algorithm == "fixed_tree":
        # fixed tree across both levels keeps the global combine order a
        # function of (pod_id, rank_id) only → reproducible multi-pod.
        x = allreduce_fixed_tree(x, inner, op=op, accum_dtype=accum_dtype)
        return allreduce_fixed_tree(x, outer, op=op, accum_dtype=accum_dtype)
    if algorithm == "psum":
        return allreduce_psum(x, (outer, inner))
    if algorithm == "ring":
        x = allreduce_ring(x, inner, op=op, stagger=stagger)
        return allreduce_ring(x, outer, op=op, stagger=stagger)
    if algorithm == "rhd":
        x = allreduce_rhd(x, inner, op=op)
        return allreduce_rhd(x, outer, op=op)
    raise ValueError(f"unknown algorithm {algorithm!r}")


def reduce_scatter(x: jax.Array, axes: tuple[str, ...], *,
                   algorithm: str = "ring", op: Op = jnp.add,
                   stagger: int = 0, ordered: bool = False) -> jax.Array:
    """Reduce-scatter over the innermost axis (+ allreduce over outer axes).

    Used by the FSDP path (``core/fsdp.py``): the backward of a parameter
    all-gather is exactly this — the leaf-switch aggregation of the
    gradient tree, with the pod level fully reduced.  ``ordered=True``
    guarantees rank ``r`` receives segment ``r`` (required when the
    placement must match a ``NamedSharding`` layout); the internal
    conventions (ring: ``r+1``, rhd: bit-reversed) are otherwise kept, as
    matched reduce-scatter/all-gather pairs don't care.
    """
    *outers, inner = axes
    p = lax.axis_size(inner)
    if x.shape[0] % p:
        raise ValueError(f"reduce_scatter: len {x.shape[0]} % {p} != 0")
    if algorithm == "ring":
        seg = ring_reduce_scatter(x, inner, op=op,
                                  stagger=-1 if ordered else stagger)
    elif algorithm == "rhd" or algorithm == "fixed_tree":
        seg = rhd_reduce_scatter(x, inner, op=op)
        if ordered:
            seg = lax.ppermute(seg, inner, _bitrev_perm(p))
    elif algorithm == "psum":
        seg = lax.psum_scatter(x, inner, tiled=True)
    else:
        raise ValueError(f"unknown algorithm {algorithm!r}")
    for ax in outers:
        seg = allreduce(seg, (ax,), algorithm="rhd" if algorithm != "psum"
                        else "psum", op=op)
    return seg


def all_gather(seg: jax.Array, axes: tuple[str, ...], *,
               algorithm: str = "ring", stagger: int = 0,
               ordered: bool = False) -> jax.Array:
    """All-gather over the innermost axis (inverse of ``reduce_scatter``)."""
    *_, inner = axes
    if algorithm == "ring":
        return ring_all_gather(seg, inner,
                               stagger=-1 if ordered else stagger)
    if algorithm in ("rhd", "fixed_tree"):
        if ordered:
            seg = lax.ppermute(seg, inner, _bitrev_perm(lax.axis_size(inner)))
        return rhd_all_gather(seg, inner)
    if algorithm == "psum":
        return lax.all_gather(seg, inner, tiled=True)
    raise ValueError(f"unknown algorithm {algorithm!r}")


# ---------------------------------------------------------------------------
# Analytic wire-byte accounting (used by the roofline and benchmarks).
# ---------------------------------------------------------------------------

def wire_bytes_per_rank(nbytes: int, p_inner: int, p_outer: int = 1, *,
                        algorithm: str) -> float:
    """Bytes each rank puts on the wire for a Z-byte allreduce."""
    z = float(nbytes)
    if algorithm == "ring":
        return 2 * z * (p_inner - 1) / p_inner * (1 if p_outer == 1 else 2)
    if algorithm == "rhd":
        return 2 * z * (p_inner - 1) / p_inner
    if algorithm == "fixed_tree":
        import math
        return z * math.log2(max(p_inner, 2)) + (
            z * math.log2(p_outer) if p_outer > 1 else 0.0)
    if algorithm in ("two_level", "hierarchical"):
        # The tree-driven schedule's wire model (DESIGN.md §11): the leaf
        # level carries ~2Z(1-1/fanin) intra-pod (RS up + AG down), and
        # the inter-level hop shrinks by the leaf fan-in — each leaf
        # switch forwards ONE aggregated segment for `fanin` inputs.
        inner = z * (p_inner - 1) / p_inner        # RS up the tree
        inner += z * (p_inner - 1) / p_inner       # AG down the tree
        outer = 2 * (z / p_inner) * (p_outer - 1) / max(p_outer, 1)
        return inner + outer
    if algorithm == "psum":
        return 2 * z * (p_inner * p_outer - 1) / (p_inner * p_outer)
    raise ValueError(algorithm)
