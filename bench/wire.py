"""Bus bytes of an allreduce: the same work whatever implements it.

Each rank of a ``p``-rank allreduce of ``nbytes`` must send, and
receive, at least ``2 (p − 1) / p · nbytes`` (reduce-scatter, then
all-gather; Patarasuk & Yuan, JPDC 2009) — the ring's count in
``repro.core.collectives.wire_bytes_per_rank``, copied here so that the
yardstick does not move with the program.
"""
from __future__ import annotations


def allreduce_bus_bytes(nbytes: float, p: int) -> float:
    if p < 1:
        raise ValueError(f"an allreduce needs p >= 1 ranks, got {p}")
    return 2.0 * (p - 1) / p * float(nbytes)
