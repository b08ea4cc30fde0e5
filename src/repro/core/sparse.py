"""In-network *sparse* allreduce (paper §7) — TPU-native adaptation.

The paper's switches aggregate (index, value) pairs: leaf switches store
partial aggregates in a hash table (+ spill buffer), the root switch in a
dense array, because sparse data *densifies* while traveling toward the
root of the reduction tree.

TPU adaptation (recorded in DESIGN.md §8): data-dependent hashing is
hostile to the vector units, so partial aggregates are kept as *sorted
coordinate lists* merged with vectorized sort/segment-combine logic —
identical traffic semantics — and the leaf→root densification becomes
**densify-on-overflow**: the recursive-doubling merge keeps (idx, val)
lists while the worst-case nnz fits under ``density_threshold · Z``; the
first step that would overflow converts to a dense accumulator (the
paper's array storage at the root) and finishes with dense fixed-tree
combines.  The whole schedule is static, so it jits cleanly.

Block bookkeeping from the paper (shard counters for split blocks, empty
block markers) is transport-level reliability machinery with no XLA
analogue — XLA collectives are reliable and complete — and lives in the
discrete-event simulator (``perfmodel/switch_sim.py``) where the paper's
quantitative claims are validated.
"""
from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.compat import axis_tuple as _axis_tuple
from repro.core import collectives as coll

#: Sentinel index marking an empty slot; sorts after every valid index.
SENTINEL = jnp.iinfo(jnp.int32).max


def sparse_k(frac: float, extent: int) -> int:
    """The single source of truth for top-k sizing.

    ``k`` derives from the **unpadded** extent of a reduction block (the
    padded size would inflate it) and is clamped to ``[1, extent]``, so
    ``frac >= 1`` keeps every element.
    """
    return max(1, min(int(extent), int(frac * extent)))


def topk_sparsify(x: jax.Array, k: int,
                  k_eff: jax.Array | int | None = None,
                  ) -> tuple[jax.Array, jax.Array]:
    """Magnitude top-k: returns (values[k], indices[k]) sorted by index.

    This is the host-side sparsification step that feeds the paper's F2
    pipeline (e.g. top-0.1%/1% gradient sparsification, SparCML-style).

    ``k`` is the static list *capacity*; ``k_eff`` (optional, may be a
    traced scalar) keeps only the ``k_eff`` largest-magnitude entries and
    sentinels out the rest — how the batched transport gives every arena
    bucket its own unpadded-extent-derived k under one uniform trace.
    """
    size = x.shape[0]
    if k > size:
        raise ValueError(f"k={k} > len(x)={size}")
    _, idx = lax.top_k(jnp.abs(x), k)
    idx = idx.astype(jnp.int32)
    if k_eff is None:
        order = jnp.argsort(idx)
        idx = idx[order]
        return x[idx], idx
    # entries come out of top_k in magnitude order: position i holds the
    # (i+1)-th largest, so masking positions >= k_eff keeps the top k_eff.
    idx = jnp.where(jnp.arange(k) < k_eff, idx, SENTINEL)
    order = jnp.argsort(idx)
    idx = idx[order]
    val = jnp.where(idx < size, x[jnp.minimum(idx, size - 1)],
                    jnp.zeros((), x.dtype))
    return val, idx


def scatter_dense(val: jax.Array, idx: jax.Array, size: int,
                  dtype=None) -> jax.Array:
    """Scatter a coordinate list into a dense vector (sentinels dropped)."""
    dtype = dtype or val.dtype
    # mode="drop" only drops out-of-range; negatives would wrap Python-style.
    idx = jnp.where(idx < 0, SENTINEL, idx)
    out = jnp.zeros((size,), dtype)
    return out.at[idx].add(val.astype(dtype), mode="drop",
                           indices_are_sorted=True, unique_indices=False)


def merge_coordinate_lists(idx_a: jax.Array, val_a: jax.Array,
                           idx_b: jax.Array, val_b: jax.Array,
                           ) -> tuple[jax.Array, jax.Array]:
    """Merge two index-sorted, index-unique coordinate lists.

    Output capacity is ``len(a) + len(b)``; duplicate indices are combined
    by addition; empty slots hold ``SENTINEL``.  This is the vectorized
    analogue of the paper's hash-table insert-or-accumulate handler; the
    two-pointer merge becomes sort + adjacent-duplicate combine, which maps
    onto the VPU instead of data-dependent branches.

    Inputs may carry a leading bucket axis ``(B, n)``: each bucket merges
    independently (one vmapped sort + cumsum scatter) — the form the
    batched transport feeds with all B arena buckets' lists at once.
    """
    if idx_a.ndim == 2:
        return jax.vmap(merge_coordinate_lists)(idx_a, val_a, idx_b, val_b)
    n = idx_a.shape[0] + idx_b.shape[0]
    idx = jnp.concatenate([idx_a, idx_b])
    val = jnp.concatenate([val_a, val_b])
    order = jnp.argsort(idx)
    idx = idx[order]
    val = val[order]
    # each input list is unique → at most 2 copies of any index, adjacent
    # after the sort.  Fold entry i+1 into entry i, then invalidate i+1.
    dup_next = jnp.concatenate([idx[1:] == idx[:-1],
                                jnp.zeros((1,), bool)])
    folded = val + jnp.where(dup_next, jnp.roll(val, -1), 0).astype(val.dtype)
    is_dup = jnp.concatenate([jnp.zeros((1,), bool), idx[1:] == idx[:-1]])
    # compact: the survivors are already in index order, so their
    # destinations are a running count of non-duplicates — an O(n) cumsum
    # scatter replaces the second full argsort the seed paid here.
    keep = ~is_dup
    dest = jnp.where(keep, jnp.cumsum(keep) - 1, n)   # n → dropped by mode
    out_idx = jnp.full((n,), SENTINEL, idx.dtype).at[dest].set(
        idx, mode="drop")
    out_val = jnp.zeros((n,), val.dtype).at[dest].set(
        jnp.where(keep, folded, 0), mode="drop")
    return out_idx, out_val


def densify_step(nnz_cap: int, size: int, density_threshold: float) -> bool:
    """Would a merge producing ``nnz_cap`` entries overflow sparse storage?"""
    return nnz_cap >= density_threshold * size or nnz_cap >= size


def _merge_over_axis(idx, val, dense, cap: int, axis: str, size: int,
                     density_threshold: float, scatter32, exchange):
    """One tree level of the sparse schedule: recursive doubling over
    ``axis`` with densify-on-overflow.

    Carries the (lists | dense) state across levels so the hierarchical
    schedule can keep coordinate lists through the inter-pod hop: the
    intra-pod level merges lists first, and the inter-pod level inherits
    whatever representation the leaf level ended with — sparse lists of
    capacity ``cap`` while they fit, a dense fp32 accumulator after the
    crossover (the paper's hash-at-the-leaves / array-at-the-root split,
    now spanning tree levels).  Returns the updated state.
    """
    p = lax.axis_size(axis)
    if not (p > 0 and (p & (p - 1)) == 0):
        raise ValueError(f"sparse merge requires power-of-two P, got {p}")
    steps = p.bit_length() - 1
    for s in range(steps):
        d = 1 << s
        perm = coll.xor_perm(p, d)
        if dense is None and densify_step(cap * 2, size, density_threshold):
            dense = scatter32(val, idx)
        if dense is None:
            idx_r, val_r = exchange(idx, val, axis, perm)
            idx, val = merge_coordinate_lists(idx, val, idx_r, val_r)
            cap *= 2
        else:
            dense = dense + lax.ppermute(dense, axis, perm)
    return idx, val, dense, cap


def _exchange_flat(idx: jax.Array, val: jax.Array, axis: str, perm,
                   ) -> tuple[jax.Array, jax.Array]:
    """Single-vector list exchange: one ppermute each for idx and val."""
    return (lax.ppermute(idx, axis, perm), lax.ppermute(val, axis, perm))


def sparse_allreduce(x: jax.Array, axis: str, k: int, *,
                     density_threshold: float = 0.25,
                     mean: bool = False,
                     k_eff: jax.Array | int | None = None,
                     ) -> tuple[jax.Array, jax.Array]:
    """Top-k sparse allreduce over one manual mesh axis.

    Each rank contributes its top-``k`` (by magnitude) elements of the
    Z-element vector ``x``.  Returns ``(reduced_dense, my_contribution)``
    where ``reduced_dense[i] = Σ_r contribution_r[i]`` and
    ``my_contribution`` is this rank's decoded (sparsified) vector — the
    caller subtracts it from ``x`` to build the error-feedback residual.

    Wire schedule (recursive doubling over P ranks, log2 P steps): while
    sparse, step s exchanges ≤ k·2^s (idx, val) pairs; once the worst-case
    merged nnz crosses ``density_threshold · Z`` the state densifies and
    the remaining steps exchange dense vectors — exactly the paper's
    hash-at-the-leaves / array-at-the-root split, with the crossover depth
    chosen statically from (k, Z, threshold).
    """
    p = lax.axis_size(axis)
    if not (p > 0 and (p & (p - 1)) == 0):
        raise ValueError(f"sparse_allreduce requires power-of-two P, got {p}")
    size = x.shape[0]

    val, idx = topk_sparsify(x, k, k_eff)
    mine = scatter_dense(val, idx, size, dtype=x.dtype)
    scatter32 = lambda v, i: scatter_dense(v, i, size, dtype=jnp.float32)

    idx, val, dense, _ = _merge_over_axis(
        idx, val, None, k, axis, size, density_threshold, scatter32,
        _exchange_flat)
    if dense is None:
        dense = scatter32(val, idx)
    if mean:
        dense = dense / p
    return dense.astype(x.dtype), mine


def _exchange_lists(idx: jax.Array, val: jax.Array, axis: str, perm,
                    ) -> tuple[jax.Array, jax.Array]:
    """ppermute a batch of coordinate lists to the XOR partner.

    For 32-bit values the (idx, val) pair travels as ONE ppermute — the
    values are bitcast to int32 and stacked with the indices, so each
    recursive-doubling step of the batched schedule issues a single
    collective carrying all B buckets' lists (bit-exact: the bitcast
    round-trips every payload, NaNs included).  Sub-32-bit floats fall
    back to two ppermutes (idx + val) — still one pair per step for the
    whole batch, never per bucket.
    """
    if val.dtype.itemsize == 4:
        packed = jnp.stack([idx, lax.bitcast_convert_type(val, jnp.int32)])
        recv = lax.ppermute(packed, axis, perm)
        return recv[0], lax.bitcast_convert_type(recv[1], val.dtype)
    return (lax.ppermute(idx, axis, perm), lax.ppermute(val, axis, perm))


def sparse_allreduce_batched(x: jax.Array, axis: str,
                             ks: Sequence[int] | int, *,
                             density_threshold: float = 0.25,
                             mean: bool = False,
                             ) -> tuple[jax.Array, jax.Array]:
    """Top-k sparse allreduce of a whole ``(B, Z)`` arena in one schedule.

    The batched form of :func:`sparse_allreduce`: every recursive-doubling
    step issues **one** ppermute carrying all B buckets' coordinate lists
    (the sort + cumsum-scatter merge vmaps cleanly over the bucket axis;
    two for sub-32-bit values), so a dtype group costs log2 P
    collectives instead of the
    O(B log P) a per-bucket loop pays.  Per bucket the combine chain —
    topk, merge order, densify crossover — is exactly the single-bucket
    schedule's, so results are bitwise-equal to that loop.

    ``ks`` gives each bucket its own k (derived from its unpadded
    extent); the static list capacity is ``max(ks)`` and smaller buckets
    mask their tails with sentinels.
    """
    p = lax.axis_size(axis)
    if not (p > 0 and (p & (p - 1)) == 0):
        raise ValueError(f"sparse_allreduce requires power-of-two P, got {p}")
    b, size = x.shape
    ks = tuple(int(k) for k in (ks if hasattr(ks, "__len__") else [ks] * b))
    if len(ks) != b:
        raise ValueError(f"got {len(ks)} ks for {b} buckets")
    k_max = max(ks)
    ks_arr = jnp.asarray(ks, jnp.int32)

    val, idx = jax.vmap(lambda v, ke: topk_sparsify(v, k_max, ke))(x, ks_arr)
    scatter = jax.vmap(lambda v, i, dt=x.dtype: scatter_dense(v, i, size,
                                                              dtype=dt))
    scatter32 = jax.vmap(lambda v, i: scatter_dense(v, i, size,
                                                    dtype=jnp.float32))
    mine = scatter(val, idx)

    idx, val, dense, _ = _merge_over_axis(
        idx, val, None, k_max, axis, size, density_threshold, scatter32,
        _exchange_lists)
    if dense is None:
        dense = scatter32(val, idx)
    if mean:
        dense = dense / p
    return dense.astype(x.dtype), mine


def _dense_outer(v: jax.Array, axis: str) -> jax.Array:
    """Dense inter-pod allreduce: rhd when the axis is a power of two,
    ring otherwise — the dense exchange must work for *any* pod count
    (it is also the fallback for meshes the sparse hierarchical merge
    cannot cross)."""
    p = lax.axis_size(axis)
    if p & (p - 1):
        return coll.allreduce_ring(v, axis)
    return coll.allreduce_rhd(v, axis)


def sparse_allreduce_two_level(x: jax.Array, inner_axis: str, outer_axis: str,
                               k: int, *, density_threshold: float = 0.25,
                               mean: bool = False,
                               k_eff: jax.Array | int | None = None,
                               ) -> tuple[jax.Array, jax.Array]:
    """Multi-pod sparse allreduce: sparse tree within the pod, dense across.

    Mirrors the paper's observation that data is densest at the root: the
    intra-pod merge runs the sparse schedule; the inter-pod exchange is
    always dense (the root switch's array storage), then the result is
    already replicated within each pod.
    """
    reduced, mine = sparse_allreduce(x, inner_axis, k,
                                     density_threshold=density_threshold,
                                     k_eff=k_eff)
    reduced = _dense_outer(reduced, outer_axis)
    if mean:
        total = lax.axis_size(inner_axis) * lax.axis_size(outer_axis)
        reduced = reduced / total
    return reduced, mine


def sparse_allreduce_two_level_batched(x: jax.Array, inner_axis: str,
                                       outer_axis: str,
                                       ks: Sequence[int] | int, *,
                                       density_threshold: float = 0.25,
                                       mean: bool = False,
                                       ) -> tuple[jax.Array, jax.Array]:
    """Batched (B, Z) form of :func:`sparse_allreduce_two_level`.

    Sparse batched schedule within the pod, then a vmapped dense rhd
    across pods — each outer exchange round carries all B buckets' dense
    vectors in one batched ppermute: log2 P_in + 2 log2 P_out ppermutes
    for power-of-two axes.
    """
    reduced, mine = sparse_allreduce_batched(
        x, inner_axis, ks, density_threshold=density_threshold)
    reduced = jax.vmap(lambda v: _dense_outer(v, outer_axis))(reduced)
    if mean:
        total = lax.axis_size(inner_axis) * lax.axis_size(outer_axis)
        reduced = reduced / total
    return reduced, mine


def sparse_allreduce_hier(x: jax.Array, inner_axis: str, outer_axes,
                          k: int, *, density_threshold: float = 0.25,
                          mean: bool = False,
                          k_eff: jax.Array | int | None = None,
                          ) -> tuple[jax.Array, jax.Array]:
    """Hierarchical sparse allreduce: coordinate lists cross the tree.

    :func:`sparse_allreduce_two_level` always goes *dense* for the
    inter-pod exchange (Z fp32 elements over the scarce links).  Here
    the leaf level merges coordinate lists intra-pod first — shrinking
    the expensive hop's payload to the merged list, capacity
    ``k·fanin`` — and the upper levels *continue the sparse recursive
    doubling across pods*, densifying only when the running capacity
    crosses ``density_threshold · Z`` (wherever in the tree that
    happens).  When gradients are genuinely sparse the inter-pod wires
    never see a dense vector at all.  ``outer_axes`` is a name or a
    tuple of names, innermost first; every reduced axis must be a
    power of two.
    """
    size = x.shape[0]
    val, idx = topk_sparsify(x, k, k_eff)
    mine = scatter_dense(val, idx, size, dtype=x.dtype)
    scatter32 = lambda v, i: scatter_dense(v, i, size, dtype=jnp.float32)

    dense: jax.Array | None = None
    cap = k
    world = 1
    for axis in (inner_axis, *_axis_tuple(outer_axes)):
        world *= lax.axis_size(axis)
        idx, val, dense, cap = _merge_over_axis(
            idx, val, dense, cap, axis, size, density_threshold, scatter32,
            _exchange_flat)
    if dense is None:
        dense = scatter32(val, idx)
    if mean:
        dense = dense / world
    return dense.astype(x.dtype), mine


def sparse_allreduce_hier_batched(x: jax.Array, inner_axis: str,
                                  outer_axes,
                                  ks: Sequence[int] | int, *,
                                  density_threshold: float = 0.25,
                                  mean: bool = False,
                                  ) -> tuple[jax.Array, jax.Array]:
    """Batched ``(B, Z)`` form of :func:`sparse_allreduce_hier`.

    Every recursive-doubling step — intra-pod *and* inter-pod — issues
    ONE ppermute carrying all B buckets' coordinate lists, so a dtype
    group costs O(log P_in + Σ log P_out) collectives and the inter-pod
    steps carry lists, not dense vectors.
    """
    b, size = x.shape
    ks = tuple(int(k) for k in (ks if hasattr(ks, "__len__") else [ks] * b))
    if len(ks) != b:
        raise ValueError(f"got {len(ks)} ks for {b} buckets")
    k_max = max(ks)
    ks_arr = jnp.asarray(ks, jnp.int32)

    val, idx = jax.vmap(lambda v, ke: topk_sparsify(v, k_max, ke))(x, ks_arr)
    scatter = jax.vmap(lambda v, i, dt=x.dtype: scatter_dense(v, i, size,
                                                              dtype=dt))
    scatter32 = jax.vmap(lambda v, i: scatter_dense(v, i, size,
                                                    dtype=jnp.float32))
    mine = scatter(val, idx)

    dense: jax.Array | None = None
    cap = k_max
    world = 1
    for axis in (inner_axis, *_axis_tuple(outer_axes)):
        world *= lax.axis_size(axis)
        idx, val, dense, cap = _merge_over_axis(
            idx, val, dense, cap, axis, size, density_threshold, scatter32,
            _exchange_lists)
    if dense is None:
        dense = scatter32(val, idx)
    if mean:
        dense = dense / world
    return dense.astype(x.dtype), mine


def expected_sparse_wire_bytes(z_elems: int, k: int, p: int, *,
                               density_threshold: float = 0.25,
                               elem_bytes: int = 4,
                               idx_bytes: int = 4) -> float:
    """Analytic wire bytes per rank for the sparse schedule (roofline aid)."""
    steps = int(math.log2(p))
    total = 0.0
    cap = k
    densified = False
    for s in range(steps):
        if not densified and densify_step(cap * 2, z_elems, density_threshold):
            densified = True
        if densified:
            total += z_elems * elem_bytes
        else:
            total += cap * (elem_bytes + idx_bytes)
            cap *= 2
    return total
