"""Packet framing for the emulated switch data plane (paper §3, §4).

Hosts carve each ``(B, S)`` dtype arena into MTU-sized packets before it
hits the wire: packet payloads are ``mtu_bytes`` of consecutive arena
elements, and every packet carries the header the sPIN handlers key on —
the reduction-block id, the packet's sequence offset within the block,
the sending child's rank, the count of valid (non-pad) elements, and
the last-packet flag the paper's completion handler uses to detect a
finished block.  Framing is *bitwise*: payload bytes are never
reinterpreted, so ``depacketize(packetize(x)) == x`` bit for bit, for
any dtype, NaNs and ragged tails included.

Depacketization reassembles from the headers, not from array position —
packets may arrive in any order (the adversarial-arrival property the
reproducibility tests exercise) and the arena still round-trips.

The reliability layer (DESIGN.md §14) rides on two extras here: every
header carries a payload checksum (``HDR_CSUM``, stamped at framing
time) so a corrupted payload is *detectable* at the switch, and
:class:`FaultPlan` / :class:`FaultSchedule` describe a deterministic,
seedable lossy fabric — which packets drop, duplicate, arrive corrupted
or reordered on each delivery round — that the data plane replays.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

#: Header field indices (one int32 each, HEADER_BYTES on the wire).
HDR_BLOCK = 0       # reduction-block (arena bucket) id
HDR_SEQ = 1         # packet sequence number within the block
HDR_CHILD = 2       # sending child's rank on the reduced axis
HDR_VALID = 3       # valid payload elements (< payload_elems on tails)
HDR_LAST = 4        # 1 on the block's final packet (completion marker)
HDR_CSUM = 5        # payload checksum (wraparound uint32 sum of elements)
HEADER_FIELDS = 6
HEADER_BYTES = HEADER_FIELDS * 4


@dataclasses.dataclass(frozen=True)
class PacketFormat:
    """The wire format: payload MTU in bytes (headers ride separately)."""

    mtu_bytes: int = 1024

    def payload_elems(self, dtype) -> int:
        """N: elements of ``dtype`` per packet payload."""
        itemsize = jnp.dtype(dtype).itemsize
        if self.mtu_bytes % itemsize:
            raise ValueError(f"mtu_bytes={self.mtu_bytes} not a multiple of "
                             f"{dtype} itemsize {itemsize}")
        return self.mtu_bytes // itemsize

    def packets_per_block(self, bucket_elems: int, dtype) -> int:
        """Packets needed to frame one S-element reduction block."""
        return max(1, math.ceil(bucket_elems / self.payload_elems(dtype)))


@dataclasses.dataclass(frozen=True)
class PacketStream:
    """A batch of framed packets: ``headers (n, 5) int32``, ``payload
    (n, E) dtype``.  Registered as a pytree so streams flow through
    ``ppermute``/``jnp.where`` wire ops leaf by leaf."""

    headers: jax.Array
    payload: jax.Array

    @property
    def num_packets(self) -> int:
        return self.payload.shape[0]


jax.tree_util.register_pytree_node(
    PacketStream,
    lambda s: ((s.headers, s.payload), None),
    lambda _, ch: PacketStream(*ch))


def packetize(arena: jax.Array, fmt: PacketFormat,
              child_rank: jax.Array | int = 0) -> PacketStream:
    """Frame a ``(B, S)`` arena into ``B * ceil(S/N)`` MTU packets.

    The tail packet of each block zero-pads to a whole payload and
    records the true element count in ``HDR_VALID``; ``child_rank`` (may
    be a traced rank scalar) stamps every header's ``HDR_CHILD``.
    """
    if arena.ndim != 2:
        raise ValueError(f"packetize wants a (B, S) arena, got {arena.shape}")
    b, s = arena.shape
    e = fmt.payload_elems(arena.dtype)
    npkt = fmt.packets_per_block(s, arena.dtype)
    payload = frame_bits(arena, npkt * e - s, (b * npkt, e))

    block = jnp.repeat(jnp.arange(b, dtype=jnp.int32), npkt)
    seq = jnp.tile(jnp.arange(npkt, dtype=jnp.int32), b)
    valid = jnp.minimum(e, s - seq * e).astype(jnp.int32)
    last = (seq == npkt - 1).astype(jnp.int32)
    child = jnp.full((b * npkt,), child_rank, jnp.int32)
    csum = payload_checksum(payload)
    headers = jnp.stack([block, seq, child, valid, last, csum], axis=1)
    return PacketStream(headers=headers, payload=payload)


def depacketize(stream: PacketStream, fmt: PacketFormat,
                num_buckets: int, bucket_elems: int) -> jax.Array:
    """Reassemble the ``(B, S)`` arena from a packet stream, bitwise.

    Packets are placed by their ``(HDR_BLOCK, HDR_SEQ)`` header, never
    by array position, so any permutation of the stream reassembles
    identically; tail padding is sliced off via the static ``S``.
    """
    e = fmt.payload_elems(stream.payload.dtype)
    npkt = fmt.packets_per_block(bucket_elems, stream.payload.dtype)
    n = num_buckets * npkt
    if stream.num_packets != n:
        raise ValueError(f"stream has {stream.num_packets} packets, plan "
                         f"wants {n} ({num_buckets} blocks x {npkt})")
    slot = stream.headers[:, HDR_BLOCK] * npkt + stream.headers[:, HDR_SEQ]
    dtype = stream.payload.dtype
    bits = lax.bitcast_convert_type(stream.payload, _uint_type(dtype))
    flat = jnp.zeros((n, e), bits.dtype).at[slot].set(bits, mode="drop")
    return unframe_bits(flat, dtype, (num_buckets, npkt * e), bucket_elems)


def frame_bits(x: jax.Array, pad: int, shape) -> jax.Array:
    """Zero-pad the last axis of ``x`` by ``pad`` and reshape to ``shape``.

    Works on the raw bits (the same-width uint image), so float payloads
    keep every bit: a float concatenate may quiet a signalling NaN.  The
    one framing step shared by :func:`packetize` and
    :meth:`FramePlan.pack`.
    """
    u = lax.bitcast_convert_type(x, _uint_type(x.dtype))
    if pad:
        u = jnp.concatenate(
            [u, jnp.zeros((*u.shape[:-1], pad), u.dtype)], axis=-1)
    return lax.bitcast_convert_type(u.reshape(shape), x.dtype)


def unframe_bits(payload: jax.Array, dtype, shape, keep: int) -> jax.Array:
    """Inverse of :func:`frame_bits`: reshape ``payload`` (any dtype of
    ``dtype``'s width) to ``shape``, keep the first ``keep`` elements of
    the last axis and return them as ``dtype``, bit for bit."""
    u = lax.bitcast_convert_type(payload, _uint_type(dtype))
    return lax.bitcast_convert_type(u.reshape(shape)[..., :keep], dtype)


# ---------------------------------------------------------------------------
# Static framing plan (batched data plane, DESIGN.md §12).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FramePlan:
    """Arena-style static pack/unpack plan for a ``(B, S)`` dtype arena.

    The batched data plane never materializes per-packet slices: every
    slot offset is a pure function of ``(B, S, dtype, fmt)``, so framing
    collapses to one pad+reshape (``pack``) and reassembly to one
    reshape+slice (``unpack``) — the same static-offset discipline as
    ``core/arena.py``.  Headers are likewise static (``headers`` /
    ``child_headers`` return numpy, computed at trace time): for the
    canonical slot order ``slot = block * npkt + seq``, every header
    field except the checksum is a function of the slot index alone.

    Bitwise contract (pinned by hypothesis in ``tests/test_switch.py``):
    ``pack`` produces exactly ``packetize(...).payload`` and ``unpack``
    inverts any slot permutation of it via header steering, for all
    dtypes, ragged tails, and arrival permutations.
    """

    num_buckets: int
    bucket_elems: int
    dtype: object
    fmt: PacketFormat

    def __post_init__(self):
        object.__setattr__(self, "dtype", jnp.dtype(self.dtype))

    @property
    def payload_elems(self) -> int:
        return self.fmt.payload_elems(self.dtype)

    @property
    def packets_per_block(self) -> int:
        return self.fmt.packets_per_block(self.bucket_elems, self.dtype)

    @property
    def num_packets(self) -> int:
        return self.num_buckets * self.packets_per_block

    @property
    def pad(self) -> int:
        return (self.packets_per_block * self.payload_elems
                - self.bucket_elems)

    def pack(self, arena: jax.Array) -> jax.Array:
        """``(..., B, S)`` arena → ``(..., n, E)`` packed payload tensor
        (canonical slot order; bitwise equal to ``packetize().payload``)."""
        *lead, b, s = arena.shape
        if (b, s) != (self.num_buckets, self.bucket_elems):
            raise ValueError(f"pack: arena {arena.shape[-2:]} != plan "
                             f"({self.num_buckets}, {self.bucket_elems})")
        return frame_bits(arena, self.pad,
                          (*lead, self.num_packets, self.payload_elems))

    def unpack(self, payload: jax.Array) -> jax.Array:
        """``(..., n, E)`` canonical-order payload → ``(..., B, S)`` arena."""
        *lead, n, e = payload.shape
        if (n, e) != (self.num_packets, self.payload_elems):
            raise ValueError(f"unpack: payload {payload.shape[-2:]} != plan "
                             f"({self.num_packets}, {self.payload_elems})")
        return unframe_bits(
            payload, payload.dtype,
            (*lead, self.num_buckets, self.packets_per_block * e),
            self.bucket_elems)

    def headers(self, child_rank: int = 0) -> np.ndarray:
        """Static ``(n, HEADER_FIELDS)`` int32 headers for the canonical
        slot order.  ``HDR_CSUM`` is left 0 — the batched plane verifies
        payload integrity against the fault schedule's static masks, not
        per-packet sums (a checksum of bits the plan itself packed would
        be circular)."""
        npkt = self.packets_per_block
        e = self.payload_elems
        block = np.repeat(np.arange(self.num_buckets, dtype=np.int32), npkt)
        seq = np.tile(np.arange(npkt, dtype=np.int32), self.num_buckets)
        valid = np.minimum(e, self.bucket_elems - seq * e).astype(np.int32)
        last = (seq == npkt - 1).astype(np.int32)
        child = np.full((self.num_packets,), child_rank, np.int32)
        csum = np.zeros((self.num_packets,), np.int32)
        return np.stack([block, seq, child, valid, last, csum], axis=1)

    def child_headers(self, num_children: int) -> np.ndarray:
        """Static ``(P, n, HEADER_FIELDS)`` headers, ``HDR_CHILD`` = the
        child's index in the gathered stack."""
        return np.stack([self.headers(child_rank=p)
                         for p in range(num_children)])


# ---------------------------------------------------------------------------
# Payload integrity (DESIGN.md §14): checksum + wire corruption.
# ---------------------------------------------------------------------------

def _uint_type(dtype) -> jnp.dtype:
    return jnp.dtype(f"uint{jnp.dtype(dtype).itemsize * 8}")


def payload_checksum(payload: jax.Array) -> jax.Array:
    """Per-packet checksum: wraparound uint32 sum of the payload's
    elements reinterpreted as unsigned integers (``(..., E) -> (...)``
    int32).  Bitwise on the payload image — any single-element change
    shifts the sum by a nonzero delta mod 2^32, so the single-element
    corruption :func:`corrupt_first_elem` injects is always detected."""
    u = lax.bitcast_convert_type(payload, _uint_type(payload.dtype))
    return jnp.sum(u.astype(jnp.uint32), axis=-1,
                   dtype=jnp.uint32).astype(jnp.int32)


def corrupt_first_elem(payload: jax.Array, mask: jax.Array) -> jax.Array:
    """Flip bits of element 0 of each masked packet (``mask`` broadcasts
    over the leading packet axes of a ``(..., E)`` payload).  The XOR
    pattern is nonzero, so a corrupted packet never equals the clean one
    and its header checksum can never validate."""
    ut = _uint_type(payload.dtype)
    u = lax.bitcast_convert_type(payload, ut)
    bits = jnp.dtype(ut).itemsize * 8
    pattern = jnp.asarray(0x5A5A5A5A5A5A5A5A & ((1 << bits) - 1), ut)
    flipped = u.at[..., 0].set(u[..., 0] ^ pattern)
    u = jnp.where(mask[..., None], flipped, u)
    return lax.bitcast_convert_type(u, payload.dtype)


# ---------------------------------------------------------------------------
# Deterministic fault injection (DESIGN.md §14).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Timeout/retransmit knobs, in *modeled rounds* (never wall clock).

    The switch waits ``timeout_rounds`` service rounds for a slot to
    complete, NACKs the missing packets, and backs the wait off
    geometrically (``timeout_rounds * backoff**(retry-1)``) for up to
    ``max_retries`` retransmission rounds before declaring the slot — and
    with it the session — lost."""

    timeout_rounds: int = 4
    max_retries: int = 3
    backoff: float = 2.0

    def wait_rounds(self, retry: int) -> float:
        """Modeled rounds waited before retransmission round ``retry``."""
        return self.timeout_rounds * self.backoff ** max(0, retry - 1)


@dataclasses.dataclass(frozen=True)
class FaultPlan:
    """A deterministic, seedable lossy fabric for the emulated switch.

    Per delivery attempt each packet independently drops with
    probability ``drop`` or arrives bit-corrupted with probability
    ``corrupt``; each retransmission round redelivers already-accepted
    packets with probability ``duplicate`` (exercising the seen-bitmap),
    and with probability ``reorder`` a round's child streams arrive
    interleaved by a random permutation (exercising header steering).
    ``levels`` restricts injection to those tree levels (``None`` = all).

    Hashable/frozen so it can ride inside ``FlareConfig``; all draws
    come from ``np.random.default_rng([seed, level, P, n])`` so a plan is
    a pure function of (plan, level, shape) — the chaos tests replay the
    exact same faults on every run and every rank."""

    seed: int = 0
    drop: float = 0.0
    duplicate: float = 0.0
    reorder: float = 0.0
    corrupt: float = 0.0
    levels: tuple[int, ...] | None = None
    retry: RetryPolicy = RetryPolicy()

    def __post_init__(self):
        for f in ("drop", "duplicate", "reorder", "corrupt"):
            v = getattr(self, f)
            if not 0.0 <= v < 1.0:
                raise ValueError(f"FaultPlan.{f}={v} outside [0, 1)")
        if self.levels is not None:
            object.__setattr__(self, "levels",
                               tuple(int(l) for l in self.levels))

    def applies(self, level: int) -> bool:
        return self.levels is None or level in self.levels

    def schedule(self, level: int, num_children: int,
                 num_packets: int) -> "FaultSchedule":
        """Materialize the per-round delivery masks for one level's
        ``(P, n)`` child stack — deterministic in (plan, level, P, n)."""
        p, n = int(num_children), int(num_packets)
        rng = np.random.default_rng([self.seed, level, p, n])
        rounds = 1 + self.retry.max_retries
        arrives = np.zeros((rounds, p, n), bool)
        corrupt = np.zeros((rounds, p, n), bool)
        perms = np.tile(np.arange(p), (rounds, 1))
        accepted = np.zeros((p, n), bool)
        retransmits = duplicates = corrupt_rejected = 0
        used = 1
        for r in range(rounds):
            attempt = ~accepted if r else np.ones((p, n), bool)
            if r and not attempt.any():
                break
            used = r + 1
            dropped = rng.random((p, n)) < self.drop
            corr = rng.random((p, n)) < self.corrupt
            arr = attempt & ~dropped
            arrives[r] = arr
            corrupt[r] = arr & corr
            if r:
                retransmits += int(attempt.sum())
                dup = accepted & (rng.random((p, n)) < self.duplicate)
                arrives[r] |= dup            # redelivered clean copies
                duplicates += int(dup.sum())
            corrupt_rejected += int((arr & corr).sum())
            accepted |= arr & ~corr
            if self.reorder and rng.random() < self.reorder:
                perms[r] = rng.permutation(p)
        return FaultSchedule(
            arrives=arrives[:used], corrupt=corrupt[:used],
            perms=perms[:used], survives=bool(accepted.all()),
            retransmits=retransmits, duplicates=duplicates,
            corrupt_rejected=corrupt_rejected,
            wait_rounds=sum(self.retry.wait_rounds(r)
                            for r in range(1, used)))


@dataclasses.dataclass(frozen=True)
class FaultSchedule:
    """One level's replayable fault trace: static numpy masks (never
    traced values — the data plane unrolls over them) plus the derived
    counters the perfmodel cross-check keys on.

    ``arrives[r, p, i]`` — child ``p``'s packet ``i`` is delivered on
    round ``r`` (round 0 = first transmission, later rounds =
    NACK-driven retransmissions and duplicate redeliveries);
    ``corrupt[r, p, i]`` — that delivery is bit-corrupted (fails the
    checksum);  ``perms[r]`` — the child interleaving of round ``r``'s
    arrivals.  ``survives`` is statically known because corruption
    deterministically fails the checksum: every clean delivery is
    accepted, everything else is rejected."""

    arrives: np.ndarray         # (R, P, n) bool
    corrupt: np.ndarray         # (R, P, n) bool
    perms: np.ndarray           # (R, P) int — per-round child interleave
    survives: bool              # all packets accepted within the budget
    retransmits: int            # NACK-driven retransmission attempts
    duplicates: int             # redeliveries of already-accepted packets
    corrupt_rejected: int       # deliveries the checksum must reject
    wait_rounds: float          # modeled backoff rounds spent waiting

    @property
    def rounds(self) -> int:
        return self.arrives.shape[0]
