"""Pallas TPU kernel: sparse (idx, val) scatter-add into a dense buffer.

This is the paper's *array storage* (§7): the root switch accumulates
incoming (index, value) pairs directly into a dense aggregation buffer.
A GPU/CPU implementation scatters through memory with indirect writes;
the PsPIN paper even proposes hardware indirection support [84].  The TPU
has no efficient data-dependent scatter inside a kernel — but it has the
MXU: scatter-add becomes a **one-hot matrix product**, turning indirect
memory traffic into dense systolic compute (profitable because the entry
list is short relative to the dense block, exactly the sparse-allreduce
regime).

Grid: (dense tiles × entry tiles), entry-major so each output tile in
VMEM accumulates over all entry tiles before moving on.  Entries outside
the current dense tile (or marked ``-1``/sentinel) contribute zero rows
in the one-hot, so no masking pass is needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _sparse_accum_kernel(idx_ref, val_ref, o_ref, *, tile_z):
    zt = pl.program_id(0)
    et = pl.program_id(1)
    idx = idx_ref[...]                            # (TILE_E,) int32, global
    val = val_ref[...].astype(jnp.float32)        # (TILE_E,)
    z_lo = zt * tile_z
    local = idx - z_lo                            # position within this tile
    e = idx.shape[0]
    # one-hot: rows for entries that land in this tile, zero rows otherwise
    cols = jax.lax.broadcasted_iota(jnp.int32, (e, tile_z), 1)
    onehot = (cols == local[:, None]).astype(jnp.float32)   # OOB rows all-zero
    contrib = val[None, :] @ onehot               # (1, TILE_Z) on the MXU

    @pl.when(et == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += contrib[0].astype(o_ref.dtype)


def sparse_accum(idx: jax.Array, val: jax.Array, size: int, *,
                 tile_z: int = 2048, tile_e: int = 512,
                 out_dtype=jnp.float32,
                 interpret: bool | None = None) -> jax.Array:
    """Dense[size] accumulation of an (idx, val) coordinate list.

    Entries with ``idx < 0`` or ``idx >= size`` are dropped (the sentinel
    convention of ``core/sparse.py`` and ``kernels/topk_compact.py``).
    Duplicate indices accumulate.  fp32 accumulation regardless of
    ``val.dtype``.
    """
    e = idx.shape[0]
    if size % tile_z:
        raise ValueError(f"sparse_accum: size={size} % tile_z={tile_z} != 0")
    tile_e = min(tile_e, e)
    if e % tile_e:
        raise ValueError(f"sparse_accum: entries={e} % tile_e={tile_e} != 0")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    kernel = functools.partial(_sparse_accum_kernel, tile_z=tile_z)
    out = pl.pallas_call(
        kernel,
        grid=(size // tile_z, e // tile_e),
        in_specs=[pl.BlockSpec((tile_e,), lambda z, t: (t,)),
                  pl.BlockSpec((tile_e,), lambda z, t: (t,))],
        out_specs=pl.BlockSpec((tile_z,), lambda z, t: (z,)),
        out_shape=jax.ShapeDtypeStruct((size,), out_dtype),
        interpret=interpret,
    )(idx, val)
    return out


def _sparse_accum_slots_kernel(idx_ref, val_ref, o_ref, *, tile_z):
    zt = pl.program_id(1)
    et = pl.program_id(2)
    idx = idx_ref[...]                            # (TILE_B, TILE_E) bucket-local
    val = val_ref[...].astype(jnp.float32)        # (TILE_B, TILE_E)
    local = idx - zt * tile_z
    rows = jax.lax.broadcasted_iota(jnp.int32, (tile_z, idx.shape[1]), 0)

    @pl.when(et == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    for r in range(idx.shape[0]):                 # static unroll over buckets
        # transposed one-hot (TILE_Z, TILE_E): OOB/sentinel columns all-zero
        onehot_t = (rows == local[r:r + 1, :]).astype(jnp.float32)
        contrib = jax.lax.dot_general(            # (1, TILE_Z) on the MXU
            val[r:r + 1, :], onehot_t, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        o_ref[r:r + 1, :] += contrib.astype(o_ref.dtype)


def sparse_accum_slots(idx: jax.Array, val: jax.Array, size: int, *,
                       tile_b: int = 8, tile_z: int = 2048, tile_e: int = 512,
                       out_dtype=jnp.float32,
                       interpret: bool | None = None) -> jax.Array:
    """Batched ``sparse_accum``: (B, E) coordinate lists → (B, size) buffers.

    The batched switch root densifies every bucket's merged coordinate
    list in one call instead of one scatter per bucket.  Indices are
    bucket-local (``0 ≤ idx < size``; out-of-range/sentinel entries drop).
    Grid is (bucket tiles × dense tiles × entry tiles) with the entry
    axis innermost, so each (buckets, dense-tile) output block
    accumulates its entry tiles in order.  A block holds ``tile_b``
    buckets: the TPU tiles the last two block dims by (8, 128), so a
    one-bucket block only compiles when ``B == 1``.
    """
    b, e = idx.shape
    tile_b = min(tile_b, b)
    if b % tile_b:
        raise ValueError(
            f"sparse_accum_slots: buckets={b} % tile_b={tile_b} != 0")
    if size % tile_z:
        raise ValueError(
            f"sparse_accum_slots: size={size} % tile_z={tile_z} != 0")
    tile_e = min(tile_e, e)
    if e % tile_e:
        raise ValueError(
            f"sparse_accum_slots: entries={e} % tile_e={tile_e} != 0")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    kernel = functools.partial(_sparse_accum_slots_kernel, tile_z=tile_z)
    return pl.pallas_call(
        kernel,
        grid=(b // tile_b, size // tile_z, e // tile_e),
        in_specs=[pl.BlockSpec((tile_b, tile_e), lambda i, z, t: (i, t)),
                  pl.BlockSpec((tile_b, tile_e), lambda i, z, t: (i, t))],
        out_specs=pl.BlockSpec((tile_b, tile_z), lambda i, z, t: (i, z)),
        out_shape=jax.ShapeDtypeStruct((b, size), out_dtype),
        interpret=interpret,
    )(idx, val)
