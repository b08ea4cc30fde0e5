"""The one traffic generator: token batches from a traffic file's numbers.

Token ids follow a Zipf law over the vocabulary, ``p(id) ∝ (id + 1)^−s``
with ``s = zipf_s`` (the rank-frequency shape of natural text), drawn
on the device.  Batch ``i`` comes from its own key, so the first batches
are the same however many a run makes, and every row differs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def token_batch(key, i: int, *, vocab: int, batch: int, seq: int,
                zipf_s: float) -> dict:
    w = jnp.arange(1, vocab + 1, dtype=jnp.float32) ** -zipf_s
    cdf = jnp.cumsum(w) / jnp.sum(w)
    u = jax.random.uniform(jax.random.fold_in(key, i), (batch, seq + 1))
    ids = jnp.minimum(jnp.searchsorted(cdf, u), vocab - 1).astype(jnp.int32)
    return {"tokens": ids[:, :-1], "labels": ids[:, 1:]}


def token_batches(key, n: int, **kw) -> list[dict]:
    return [token_batch(key, i, **kw) for i in range(n)]
