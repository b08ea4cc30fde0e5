"""Gradient transport compression (paper F1: custom data types).

The paper's switch aggregates int8/int16/int32/fp16/fp32 elements and
vectorizes sub-word types ("the HPUs ... can aggregate two int16 elements
in a single cycle").  The TPU-native analogue is *quantized transport*:
gradients are blockwise-quantized to int8 with a per-chunk fp32 scale,
moved over the wire at 1/4 width, accumulated in fp32, and re-quantized
for the broadcast leg.  Error feedback keeps the quantization bias out of
the optimizer trajectory (standard for compressed allreduce).

``quantized_allreduce`` implements the wire protocol with one
``lax.all_to_all`` (the reduce-scatter leg: each rank receives everyone's
copy of its chunk, int8) and one ``lax.all_gather`` (the broadcast leg,
int8 again) — total wire bytes ≈ 2·Z/4 instead of 2·Z.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.compat import axis_tuple as _axis_tuple

INT8_MAX = 127.0


def quantize_int8(x: jax.Array, block: int = 256) -> tuple[jax.Array, jax.Array]:
    """Blockwise symmetric int8 quantization.

    Returns ``(q, scales)`` with ``q`` int8 of x.shape (flat along the
    last axis, padded by the caller to a multiple of ``block``) and
    ``scales`` fp32 of shape ``(*lead, n // block)``.  Leading axes (the
    arena bucket axis) vectorize: each bucket quantizes exactly as the
    flat form would.
    """
    *lead, n = x.shape
    if n % block:
        raise ValueError(f"quantize_int8: len {n} % {block} != 0")
    xb = x.reshape(*lead, n // block, block).astype(jnp.float32)
    scale = jnp.max(jnp.abs(xb), axis=-1, keepdims=True) / INT8_MAX
    scale = jnp.maximum(scale, 1e-30)
    q = jnp.clip(jnp.round(xb / scale), -INT8_MAX, INT8_MAX).astype(jnp.int8)
    return q.reshape(x.shape), scale[..., 0]


def dequantize_int8(q: jax.Array, scales: jax.Array, block: int = 256,
                    dtype=jnp.float32) -> jax.Array:
    *lead, n = q.shape
    qb = q.reshape(*lead, n // block, block).astype(jnp.float32)
    return (qb * scales[..., None]).reshape(q.shape).astype(dtype)


def _pad_last(x: jax.Array, m: int) -> tuple[jax.Array, int]:
    """Pad the last axis of ``x`` to a multiple of ``m``; return (padded, n)."""
    n = x.shape[-1]
    rem = (-n) % m
    if rem:
        pad = jnp.zeros(x.shape[:-1] + (rem,), x.dtype)
        x = jnp.concatenate([x, pad], axis=-1)
    return x, n


def quantized_reduce_scatter(x: jax.Array, axis: str, *, block: int = 256,
                             ) -> tuple[jax.Array, int]:
    """The reduce-scatter leg of the int8 wire protocol (steps 1–3).

    Quantize P chunks blockwise, ``all_to_all`` so rank r holds every
    rank's int8 copy of chunk r, dequantize and accumulate in fp32 (the
    switch's "FPU in every HPU").  Returns ``(red, n)``: the rank's fp32
    reduced chunk — the leaf switch's aggregation buffer — and the
    unpadded input length, which :func:`quantized_all_gather` needs to
    invert the pad.
    """
    p = lax.axis_size(axis)
    # pad so each of the P chunks is a multiple of `block`
    xp, n = _pad_last(x, p * block)
    chunk_len = xp.shape[0] // p

    q, scales = quantize_int8(xp, block)                    # (Z,), (Z/block,)
    q = q.reshape(p, chunk_len)
    scales = scales.reshape(p, chunk_len // block)

    # all_to_all: axis 0 is the chunk/destination index.
    qt = lax.all_to_all(q, axis, split_axis=0, concat_axis=0, tiled=True)
    st = lax.all_to_all(scales, axis, split_axis=0, concat_axis=0, tiled=True)
    qt = qt.reshape(p, chunk_len)
    st = st.reshape(p, chunk_len // block)

    # local fp32 accumulation of everyone's copy of my chunk
    deq = qt.astype(jnp.float32).reshape(p, chunk_len // block, block)
    deq = deq * st[:, :, None]
    return jnp.sum(deq, axis=0).reshape(chunk_len), n       # fp32


def quantized_all_gather(red: jax.Array, axis: str, *, block: int = 256,
                         dtype=jnp.float32, n: int | None = None) -> jax.Array:
    """The broadcast leg (steps 4–5): requantize + ``all_gather`` int8."""
    qr, sr = quantize_int8(red, block)
    qg = lax.all_gather(qr, axis, tiled=True)               # (Z,) int8
    sg = lax.all_gather(sr, axis, tiled=True)               # (Z/block,) fp32
    out = dequantize_int8(qg, sg, block, dtype=dtype)
    return out if n is None else out[:n]


def quantized_allreduce(x: jax.Array, axis: str, *, block: int = 256,
                        mean: bool = False) -> jax.Array:
    """int8-transport allreduce over one manual mesh axis.

    Wire protocol (Z elements, P ranks):
      1. split into P chunks; quantize each chunk blockwise → int8 + scales;
      2. ``all_to_all``: rank r receives every rank's int8 copy of chunk r
         (Z/P · P = Z int8 bytes on the wire per rank);
      3. dequantize to fp32, reduce locally (exact fp32 accumulation — the
         switch's "FPU in every HPU");
      4. re-quantize the reduced chunk, ``all_gather`` int8 + scales back
         (Z int8 bytes);
      5. dequantize.

    The result carries quantization error from steps 1 and 4 only (one
    round each way), matching the paper's transport-precision trade; use
    ``error_feedback_step`` to fold the residual into the next iteration.
    """
    red, n = quantized_reduce_scatter(x, axis, block=block)
    if mean:
        red = red / lax.axis_size(axis)
    return quantized_all_gather(red, axis, block=block, dtype=x.dtype, n=n)


def quantized_allreduce_hier(x: jax.Array, inner_axis: str, outer_axes,
                             *, block: int = 256,
                             mean: bool = False) -> jax.Array:
    """Hierarchical int8 allreduce over a multi-level reduction tree.

    The flat schedule pays full-Z quantized legs on *every* axis; here
    only the leaf level sees Z: reduce-scatter intra-pod (leaf-switch
    aggregation, Z int8 on intra-pod wires), quantized allreduce of the
    owned ``Z/fanin`` segment across each upper level (the tree's upper
    switches — the expensive inter-pod hops shrink by the leaf fan-in),
    then requantize + all-gather back down (root multicast).  One extra
    quantization round per upper level is the price of keeping those
    hops at ``Z/fanin``.  ``outer_axes`` is a name or a tuple of names,
    innermost first.
    """
    red, n = quantized_reduce_scatter(x, inner_axis, block=block)
    world = lax.axis_size(inner_axis)
    for ax in _axis_tuple(outer_axes):
        red = quantized_allreduce(red, ax, block=block)
        world *= lax.axis_size(ax)
    if mean:
        red = red / world
    return quantized_all_gather(red, inner_axis, block=block, dtype=x.dtype,
                                n=n)


def quantized_reduce_scatter_batched(x: jax.Array, axis: str, *,
                                     block: int = 256,
                                     ) -> tuple[jax.Array, int]:
    """Reduce-scatter leg for a whole ``(B, Z)`` arena: ONE ``all_to_all``
    (plus one for scales) carries every bucket's int8 chunks."""
    p = lax.axis_size(axis)
    b = x.shape[0]
    xp, n = _pad_last(x, p * block)
    chunk = xp.shape[-1] // p

    q, scales = quantize_int8(xp, block)            # (B, Zp), (B, Zp/block)
    q = q.reshape(b, p, chunk)
    scales = scales.reshape(b, p, chunk // block)

    # one exchange for all B buckets: axis 1 is the chunk/destination index
    qt = lax.all_to_all(q, axis, split_axis=1, concat_axis=1, tiled=True)
    st = lax.all_to_all(scales, axis, split_axis=1, concat_axis=1, tiled=True)

    # local fp32 accumulation of everyone's copy of my chunk, per bucket
    deq = qt.astype(jnp.float32).reshape(b, p, chunk // block, block)
    deq = deq * st[:, :, :, None]
    return jnp.sum(deq, axis=1).reshape(b, chunk), n        # fp32


def quantized_all_gather_batched(red: jax.Array, axis: str, *,
                                 block: int = 256, dtype=jnp.float32,
                                 n: int | None = None) -> jax.Array:
    """Broadcast leg for a ``(B, chunk)`` arena: ONE ``all_gather`` pair."""
    qr, sr = quantize_int8(red, block)
    qg = lax.all_gather(qr, axis, axis=1, tiled=True)        # (B, Zp) int8
    sg = lax.all_gather(sr, axis, axis=1, tiled=True)        # (B, Zp/blk)
    out = dequantize_int8(qg, sg, block, dtype=dtype)
    return out if n is None else out[:, :n]


def quantized_allreduce_batched(x: jax.Array, axis: str, *, block: int = 256,
                                mean: bool = False) -> jax.Array:
    """int8-transport allreduce of a whole ``(B, Z)`` arena.

    The batched form of :func:`quantized_allreduce`: ONE ``all_to_all``
    moves every bucket's int8 chunks (plus one for the scales) and ONE
    ``all_gather`` pair brings the requantized sums back — two
    ``all_to_all``s and two ``all_gather``s whatever B, O(1)
    collectives per dtype group instead of the O(B) a per-bucket loop
    pays.  Per bucket the quantize → exchange → fp32 accumulate →
    requantize chain is exactly the flat form's, so results are
    bitwise-equal to that loop.
    """
    red, n = quantized_reduce_scatter_batched(x, axis, block=block)
    if mean:
        red = red / lax.axis_size(axis)
    return quantized_all_gather_batched(red, axis, block=block, dtype=x.dtype,
                                        n=n)


def quantized_allreduce_hier_batched(x: jax.Array, inner_axis: str,
                                     outer_axes, *, block: int = 256,
                                     mean: bool = False) -> jax.Array:
    """Batched ``(B, Z)`` form of :func:`quantized_allreduce_hier`.

    Still O(1) collectives per dtype group — one ``all_to_all`` pair
    intra-pod, one ``all_to_all`` + ``all_gather`` pair per upper level
    at ``Z/fanin``, one ``all_gather`` pair back — with every exchange
    carrying all B buckets.
    """
    red, n = quantized_reduce_scatter_batched(x, inner_axis, block=block)
    world = lax.axis_size(inner_axis)
    for ax in _axis_tuple(outer_axes):
        red = quantized_allreduce_batched(red, ax, block=block)
        world *= lax.axis_size(ax)
    if mean:
        red = red / world
    return quantized_all_gather_batched(red, inner_axis, block=block,
                                        dtype=x.dtype, n=n)


def error_feedback_step(grad: jax.Array, ef: jax.Array,
                        transmit_fn) -> tuple[jax.Array, jax.Array]:
    """One EF-compressed reduction step.

    ``transmit_fn(v)`` must return the (lossy) reduced version of ``v``.
    Returns ``(reduced, new_ef)`` where ``new_ef = v - local_decode(v)``.
    For allreduce the residual is taken against the rank's own lossy
    encoding, which is what accumulates into the next step.
    """
    v = grad + ef
    reduced, local_decode = transmit_fn(v)
    new_ef = v - local_decode
    return reduced, new_ef


def quantize_roundtrip(x: jax.Array, block: int = 256) -> jax.Array:
    """What this rank's contribution looks like after encode+decode.

    Accepts leading batch axes (the arena bucket axis); padding and the
    quantization blocks run along the last axis.
    """
    xp, n = _pad_last(x, block)
    q, s = quantize_int8(xp, block)
    return dequantize_int8(q, s, block, dtype=x.dtype)[..., :n]
