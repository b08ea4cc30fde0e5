"""Flat-arena gradient packing: one padded buffer per dtype (paper §4/§6.2).

The paper's hosts carve the Z-element gradient into equal reduction
blocks and keep B of them in flight against the switch's aggregation
buffers.  The seed implementation packed every block with per-leaf
``jnp.concatenate`` calls and dispatched blocks one at a time; this
module replaces that with a **plan computed once per pytree structure**:

  * all same-dtype leaves live back-to-back in one flat arena, padded at
    the tail only, so *pack* is a single concatenate (leaves + one zero
    tail) and a reshape to ``(num_buckets, bucket_elems)``;
  * *unpack* is a static slice table — ``lax.slice`` at precomputed
    offsets — since bucket boundaries are a pure reshape view, leaves may
    straddle them freely (the reduction is elementwise across ranks);
  * padding is folded into the plan (``bucket_elems`` is rounded up to
    each group's pad multiple, which its transport decides) so the
    collectives never re-pad at runtime;
  * equal-size buckets become the leading axis of one array, which is
    what lets ``GradReducer`` reduce all B blocks with one batched
    schedule instead of B traced calls.

Plans are cached by (leaf shapes/dtypes, bucket_bytes, pad multiples) —
building one is pure Python bookkeeping, no tracing.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """Where one pytree leaf lives inside its dtype arena."""

    leaf_id: int                 # position in the flattened pytree
    offset: int                  # element offset into the flat arena
    size: int                    # flattened element count
    shape: tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class DtypeArena:
    """One dtype's padded flat buffer, viewed as equal-size buckets."""

    dtype: Any
    num_buckets: int             # B — reduction blocks in flight
    bucket_elems: int            # S — elements per block (padded)
    stagger_base: int            # global bucket index of bucket 0 (§5)
    slots: tuple[LeafSlot, ...]

    @property
    def total_elems(self) -> int:
        return self.num_buckets * self.bucket_elems

    @property
    def used_elems(self) -> int:
        return sum(s.size for s in self.slots)

    @property
    def valid_extents(self) -> tuple[int, ...]:
        """Unpadded element count of each bucket.

        Slots tile the arena contiguously from offset 0 and padding lives
        only at the tail, so bucket ``b`` holds real data in its first
        ``min(S, used - b*S)`` elements.  Size-derived transport knobs
        (the sparse top-k, quantization block counts) are computed from
        these extents, never from the padded ``bucket_elems`` — the
        padded size would inflate k with the padding (see
        ``sparse.sparse_k``).
        """
        used = self.used_elems
        return tuple(
            max(0, min(self.bucket_elems, used - b * self.bucket_elems))
            for b in range(self.num_buckets))

    def staggers(self, enabled: bool = True) -> np.ndarray:
        """Per-bucket ring-phase offsets (staggered sending, §5), static:
        the dense ring groups buckets by them at trace time."""
        if not enabled:
            return np.zeros((self.num_buckets,), np.int32)
        return self.stagger_base + np.arange(self.num_buckets,
                                             dtype=np.int32)

    def pack(self, leaves: Sequence[jax.Array]) -> jax.Array:
        """Gather this dtype's leaves into the (B, S) arena buffer.

        Writes through chained ``dynamic_update_slice`` at the static
        plan offsets rather than ``jnp.concatenate``: XLA aliases the
        chain into in-place stores on one buffer, and — decisive for the
        hot path — the collectives' chunk slices then read a plain
        materialized array.  (A concatenate fuses into every ring
        round's chunk extraction as a per-element multi-way select,
        which measured ~7× slower end-to-end on CPU.)
        """
        flat = jnp.zeros((self.total_elems,), self.dtype)
        for s in self.slots:
            flat = lax.dynamic_update_slice(
                flat, leaves[s.leaf_id].reshape(-1), (s.offset,))
        return flat.reshape(self.num_buckets, self.bucket_elems)

    def unpack(self, arena: jax.Array,
               out: list[jax.Array | None]) -> None:
        """Scatter a reduced (B, S) arena back into ``out`` by slot."""
        flat = arena.reshape(self.total_elems)
        for s in self.slots:
            piece = lax.slice(flat, (s.offset,), (s.offset + s.size,))
            out[s.leaf_id] = piece.reshape(s.shape)


@dataclasses.dataclass(frozen=True)
class FlatArena:
    """The full plan: one DtypeArena per distinct leaf dtype."""

    groups: tuple[DtypeArena, ...]
    num_leaves: int

    @property
    def num_buckets(self) -> int:
        return sum(g.num_buckets for g in self.groups)

    def pack(self, leaves: Sequence[jax.Array]) -> list[jax.Array]:
        return [g.pack(leaves) for g in self.groups]

    def unpack(self, arenas: Sequence[jax.Array]) -> list[jax.Array]:
        out: list[jax.Array | None] = [None] * self.num_leaves
        for g, a in zip(self.groups, arenas):
            g.unpack(a, out)
        return out


def _leaf_key(leaf) -> tuple:
    shape = tuple(leaf.shape)
    return (shape, jnp.dtype(leaf.dtype).name)


@functools.lru_cache(maxsize=256)
def _build_cached(keys: tuple, bucket_bytes: int, pad_multiple: int,
                  group_pads: tuple = ()) -> FlatArena:
    pads = dict(group_pads)
    by_dtype: dict[str, list[int]] = {}
    for i, (_, dtype_name) in enumerate(keys):
        by_dtype.setdefault(dtype_name, []).append(i)

    groups: list[DtypeArena] = []
    stagger_base = 0
    for dtype_name in sorted(by_dtype):
        dtype = jnp.dtype(dtype_name)
        ids = by_dtype[dtype_name]
        slots: list[LeafSlot] = []
        off = 0
        for i in ids:
            shape = keys[i][0]
            size = int(np.prod(shape)) if shape else 1
            slots.append(LeafSlot(i, off, size, shape))
            off += size
        total = off
        total_bytes = total * dtype.itemsize
        b = max(1, math.ceil(total_bytes / bucket_bytes))
        s = math.ceil(total / b)
        pad = pads.get(dtype_name, pad_multiple)
        s = max(pad, math.ceil(s / pad) * pad)
        # shrink B if padding made later buckets entirely empty
        b = max(1, math.ceil(total / s))
        groups.append(DtypeArena(dtype, b, s, stagger_base, tuple(slots)))
        stagger_base += b
    return FlatArena(tuple(groups), len(keys))


def build_plan(leaves: Sequence[jax.Array | jax.ShapeDtypeStruct],
               bucket_bytes: int = 4 << 20, *,
               pad_multiple: int = 1,
               group_pads: Sequence[tuple[str, int]] = ()) -> FlatArena:
    """Compute (or fetch) the arena plan for a sequence of leaves.

    ``pad_multiple`` folds the collectives' divisibility requirement into
    the plan (``Transport.pad_multiple`` says what a schedule needs);
    the default 1 leaves every bucket unpadded.  ``group_pads`` gives
    some dtype groups, by dtype name, a multiple of their own.
    """
    return _build_cached(tuple(_leaf_key(l) for l in leaves),
                         int(bucket_bytes), int(pad_multiple),
                         tuple(sorted((str(n), int(m))
                                      for n, m in group_pads)))
