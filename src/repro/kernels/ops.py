"""Public jit'd wrappers over the Pallas kernels.

These handle padding/reshaping and interpret-mode dispatch (kernels run
``interpret=True`` off-TPU so CPU tests execute the same kernel bodies).
The four switch-handler wrappers (``tree_reduce``, ``tree_reduce_slots``,
``dequant_accum_slots``, ``sparse_accum_slots``) pad any shape up to
their tiling and slice the result, so on a TPU they always run the
compiled kernel; only off-TPU may the slot folds and the densify step
route to their ``kernels/ref.py`` oracle.

XLA cannot partition a Mosaic kernel: on a TPU these calls must sit
where every mesh axis is manual (``train.trainer`` makes its size-1
axes manual for this).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import quant as _quant
from repro.kernels import ref as _ref
from repro.kernels import sparse_accum as _sa
from repro.kernels import topk_compact as _tk
from repro.kernels import tree_reduce as _tr


def _on_tpu() -> bool:
    """Kernels compile for a TPU backend and are interpreted elsewhere."""
    return jax.default_backend() == "tpu"


def _pad_to(x, axis: int, m: int, value=0):
    """Pad ``axis`` of ``x`` up to a multiple of ``m`` with ``value``."""
    rem = (-x.shape[axis]) % m
    if not rem:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, rem)
    return jnp.pad(x, widths, constant_values=value)


def _tile(n: int, tiles) -> int:
    """The largest of ``tiles`` that divides ``n``."""
    return next(t for t in tiles if n % t == 0)


#: The TPU tiles the last two dims of a block by (8 sublanes, 128 lanes).
_SUBLANES, _LANES = 8, 128
#: Slot-axis block heights, largest first: S pads to a multiple of 8 only.
_SLOT_TILES = (64, 32, 16, 8)


@functools.partial(jax.jit, static_argnames=("tile_n", "accum_dtype"))
def tree_reduce(x: jax.Array, tile_n: int = 2048,
                accum_dtype=None) -> jax.Array:
    """Fixed-tree reduce of a (P, N) stack over axis 0 (pads P to pow2).

    ``accum_dtype`` defaults to fp32 for floating inputs (the F3
    reproducible accumulator) and to the input dtype for integers —
    integer sums must stay exact, never round through fp32.
    """
    p, n = x.shape
    if accum_dtype is None:
        accum_dtype = (jnp.float32 if jnp.issubdtype(x.dtype, jnp.floating)
                       else x.dtype)
    pp = 1 << max(0, (p - 1).bit_length())
    if pp != p:
        x = jnp.concatenate([x, jnp.zeros((pp - p, n), x.dtype)])
    # the fold is elementwise over N, so zero columns past N never touch
    # the bits of the first N; a 1-D bf16 block needs >= 2 x 128 lanes
    tile = min(tile_n, n + (-n) % (2 * _LANES))
    out = _tr.tree_reduce(_pad_to(x, 1, tile), tile_n=tile,
                          accum_dtype=accum_dtype, interpret=not _on_tpu())
    return out[:n]


@functools.partial(jax.jit, static_argnames=("tile_e", "accum_dtype"))
def tree_reduce_slots(x: jax.Array, tile_e: int | None = None,
                      accum_dtype=None) -> jax.Array:
    """Fixed-tree reduce of a packed (P, S, E) slot stack over axis 0.

    Slot-axis companion to :func:`tree_reduce` for the batched switch
    data plane (pads P to pow2 with zero children — absorbing under +).

    Off-TPU the interpreted Pallas grid costs more than the fold it
    runs, and the pure-jnp oracle executes the *same* aligned-pair add
    sequence (bitwise identical — pinned in ``tests/test_kernels.py``),
    so dispatch follows the backend.
    """
    p, s, e = x.shape
    if accum_dtype is None:
        accum_dtype = (jnp.float32 if jnp.issubdtype(x.dtype, jnp.floating)
                       else x.dtype)
    pp = 1 << max(0, (p - 1).bit_length())
    if pp != p:
        x = jnp.concatenate([x, jnp.zeros((pp - p, s, e), x.dtype)])
    if not _on_tpu():
        return _ref.tree_reduce(x, accum_dtype=accum_dtype)
    # S pads to the (8, 128) sublane tiling; the fold is elementwise per
    # slot, so the padded slots never change the bits of the real ones
    x = _pad_to(x, 1, _SUBLANES)
    out = _tr.tree_reduce_slots(x, tile_s=_tile(x.shape[1], _SLOT_TILES),
                                tile_e=tile_e, accum_dtype=accum_dtype,
                                interpret=False)
    return out[:s]


@functools.partial(jax.jit, static_argnames=("qblock",))
def quantize(x: jax.Array, qblock: int = 256):
    n = x.shape[0]
    if n % qblock:
        return _ref.quantize(_pad_to(x, 0, qblock), qblock)
    nb = n // qblock
    tile_b = 64 if nb % 64 == 0 else (8 if nb % 8 == 0 else 1)
    return _quant.quantize(x, qblock=qblock, tile_b=tile_b)


@functools.partial(jax.jit, static_argnames=("qblock", "out_dtype"))
def dequantize(q: jax.Array, scales: jax.Array, qblock: int = 256,
               out_dtype=jnp.float32):
    nb = q.shape[0] // qblock
    tile_b = 64 if nb % 64 == 0 else (8 if nb % 8 == 0 else 1)
    return _quant.dequantize(q, scales, qblock=qblock, tile_b=tile_b,
                             out_dtype=out_dtype)


@functools.partial(jax.jit, static_argnames=("qblock",))
def dequant_accum(q: jax.Array, scales: jax.Array,
                  qblock: int = 256) -> jax.Array:
    """Fused dequantize + fold of a (P, n) int8 child stack → (n,) fp32.

    The emulated switch's int8 payload handler (single-buffer design):
    P children's packets dequant-accumulate into one fp32 buffer in
    stack (arrival) order.
    """
    p, n = q.shape
    if n % qblock:
        # no ragged fallback: the caller already owns (P, n/qblock)
        # scales, so a ragged n means the scales shape is wrong too
        raise ValueError(f"dequant_accum: n={n} % qblock={qblock} != 0")
    nb = n // qblock
    tile_b = 64 if nb % 64 == 0 else (8 if nb % 8 == 0 else 1)
    return _quant.dequant_accum(q, scales, qblock=qblock, tile_b=tile_b)


@functools.partial(jax.jit, static_argnames=("qblock",))
def dequant_accum_slots(q: jax.Array, scales: jax.Array,
                        qblock: int = 256) -> jax.Array:
    """Fused dequant + fold of a (P, S, E) slot stack → (S, E) fp32.

    Batched-switch companion to :func:`dequant_accum`: the scales
    sideband is packed per slot as ``(P, S, E // qblock)``.
    """
    p, s, e = q.shape
    if e % qblock:
        # same contract as dequant_accum: the caller owns the per-slot
        # scales layout, so a ragged E means the scales shape is wrong
        raise ValueError(f"dequant_accum_slots: E={e} % qblock={qblock} != 0")
    q = _pad_to(q, 1, _SUBLANES)
    scales = _pad_to(scales, 1, _SUBLANES)
    out = _quant.dequant_accum_slots(q, scales, qblock=qblock,
                                     tile_s=_tile(q.shape[1], _SLOT_TILES),
                                     interpret=not _on_tpu())
    return out[:s]


@functools.partial(jax.jit, static_argnames=("k", "block"))
def topk_compact(x: jax.Array, k: int, block: int = 512):
    """Per-block magnitude top-k → (values, local indices), -1 padded."""
    n = x.shape[0]
    if n % block:
        x = _pad_to(x, 0, block)
        n = x.shape[0]
    nb = n // block
    tile_b = 8 if nb % 8 == 0 else 1
    return _tk.topk_compact(x, k, block=block, tile_b=tile_b)


@functools.partial(jax.jit, static_argnames=("size", "out_dtype"))
def sparse_accum(idx: jax.Array, val: jax.Array, size: int,
                 out_dtype=jnp.float32) -> jax.Array:
    """Scatter-add coordinate list into dense[size] (−1 entries dropped)."""
    e = idx.shape[0]
    tile_z = 2048 if size % 2048 == 0 else (256 if size % 256 == 0 else 0)
    tile_e = 512 if e % 512 == 0 else (64 if e % 64 == 0 else (8 if e % 8 == 0
                                                               else 0))
    if not tile_z or not tile_e:
        return _ref.sparse_accum(idx, val, size, out_dtype)
    return _sa.sparse_accum(idx, val, size, tile_z=tile_z, tile_e=tile_e,
                            out_dtype=out_dtype)


@functools.partial(jax.jit, static_argnames=("size", "out_dtype"))
def sparse_accum_slots(idx: jax.Array, val: jax.Array, size: int,
                       out_dtype=jnp.float32) -> jax.Array:
    """Batched scatter-add: (B, E) bucket-local lists → (B, size) buffers.

    The batched switch root's densify step — one kernel over all buckets
    instead of a per-bucket scatter.  Sentinel (<0) entries drop.

    The one-hot-matmul kernel is an MXU trick: it beats indirect writes
    only where indirect writes are expensive (TPU).  Off-TPU the
    interpreted grid loops a tiny matmul thousands of times while the
    backend has a perfectly good native scatter, so dispatch follows the
    backend.  On a TPU any shape pads to the kernel's tiling: buckets to
    8 and entries to 128 with dropped (-1) entries, the dense size to
    128 with columns sliced off after.
    """
    b, e = idx.shape
    if not _on_tpu():
        return _ref.sparse_accum_slots(idx, val, size, out_dtype)
    idx = _pad_to(_pad_to(idx, 0, _SUBLANES, -1), 1, _LANES, -1)
    val = _pad_to(_pad_to(val, 0, _SUBLANES), 1, _LANES)
    size_p = size + (-size) % _LANES
    out = _sa.sparse_accum_slots(
        idx, val, size_p, tile_b=_SUBLANES,
        tile_z=_tile(size_p, (2048, 1024, 512, 256, 128)),
        tile_e=_tile(idx.shape[1], (512, 256, 128)),
        out_dtype=out_dtype, interpret=False)
    return out[:b, :size]


def blockwise_sparsify(x: jax.Array, k: int, block: int = 512):
    """Global (values, indices) from per-block top-k (SparCML packetization).

    Returns flat value/index vectors of length ``(n/block)·k`` with global
    indices, index-sorted, sentinel −1 → dropped by ``sparse_accum``.
    """
    vals, idx = topk_compact(x, k, block)
    nb = vals.shape[0]
    base = (jnp.arange(nb, dtype=jnp.int32) * block)[:, None]
    # drop zero-valued tie fills: they carry no information on the wire
    gidx = jnp.where((idx >= 0) & (vals != 0), idx + base, -1)
    return vals.reshape(-1), gidx.reshape(-1)
