"""Zamba2 hybrid: Mamba-2 backbone + *shared* attention blocks.

Every ``hybrid_attn_every`` mamba layers, one transformer block runs with
parameters **shared across all its applications** (arXiv:2411.15242).
Shared parameters receive summed gradients from every reuse site — the
arch in the pool where Flare's reproducible reduction (F3) matters most,
since those sums span both the layer-reuse sites and the data axis.

Layout: ``n_layers`` mamba layers split into full groups of
``hybrid_attn_every`` (outer scan; shared block applied after each group)
plus a remainder scanned at the end.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.models import base, mamba2
from repro.models import transformer as tf
from repro.models.base import ModelConfig

Gather = Callable | None


def _groups(cfg: ModelConfig) -> tuple[int, int]:
    g = cfg.hybrid_attn_every
    return cfg.n_layers // g, cfg.n_layers % g


def init_params(cfg: ModelConfig, key) -> dict:
    ks = jax.random.split(key, 4)
    p = mamba2.init_params(cfg, ks[0])
    # one shared transformer block (attn + mlp)
    p["shared_block"] = tf._layer_params(cfg, ks[1], moe=False)
    return p


def _g(gather: Gather, lp):
    return gather(lp) if gather is not None else lp


def _run(cfg: ModelConfig, params, x, *, mode: str, cache=None, pos=None,
         gather: Gather = None):
    ngroups, rem = _groups(cfg)
    g = cfg.hybrid_attn_every
    want_cache = mode in ("prefill", "decode")
    b = x.shape[0]

    mstack = params["layers"]
    grouped = jax.tree.map(
        lambda a: a[:ngroups * g].reshape((ngroups, g) + a.shape[1:]), mstack)
    tail = jax.tree.map(lambda a: a[ngroups * g:], mstack)
    shared = params["shared_block"]

    def mamba_body(carry, xs):
        x = carry
        lp, lcache = xs
        lp = _g(gather, lp)
        c = lcache if mode == "decode" else (
            mamba2._zero_layer_cache(cfg, x.shape[0])
            if mode == "prefill" else None)
        h = base.rmsnorm(x, lp["ln"], cfg.norm_eps)
        out, nc = mamba2.mamba_block(cfg, lp, h, cache=c)
        out = base.tag_block_out(cfg, out)
        return x + out, (nc if want_cache else None)

    mb = base.remat(cfg, mamba_body) if mode == "train" else mamba_body

    def group_body(carry, xs):
        x = carry
        gstack, gmcache, gacache = xs
        x, mys = jax.lax.scan(mb, x, (gstack, gmcache))
        sp = _g(gather, shared)
        c = None
        if mode == "decode":
            c = dict(gacache)
            c["pos"] = pos
        po = pos if mode != "train" else None
        x, kv, _ = tf._self_layer(cfg, sp, x, moe=False, cache=c,
                                  pos_offset=po)
        ays = {"k": kv[0], "v": kv[1]} if want_cache else None
        return x, (mys, ays)

    if mode == "decode":
        gm = jax.tree.map(
            lambda a: a[:ngroups * g].reshape((ngroups, g) + a.shape[1:]),
            cache["mamba"])
        tail_c = jax.tree.map(lambda a: a[ngroups * g:], cache["mamba"])
        ga = cache["attn"]
    else:
        gm = jnp.zeros((ngroups, g, 0))
        tail_c = jnp.zeros((rem, 0))
        ga = jnp.zeros((ngroups, 0))

    x, (mys, ays) = jax.lax.scan(group_body, x, (grouped, gm, ga))
    if rem:
        x, tys = jax.lax.scan(mb, x, (tail, tail_c))
    else:
        tys = None

    if want_cache:
        mcache = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), mys)
        if rem:
            mcache = jax.tree.map(lambda a, t: jnp.concatenate([a, t], 0),
                                  mcache, tys)
        return x, {"mamba": mcache, "attn": ays}
    return x, None


def loss_fn(cfg: ModelConfig, params, batch, *, gather: Gather = None,
            loss_chunk: int = 2048):
    tokens, labels = batch["tokens"], batch["labels"]
    x, emb = tf._embed(cfg, params, tokens, gather)
    x, _ = _run(cfg, params, x, mode="train", gather=gather)
    x = base.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = tf._head(cfg, params, emb, gather)
    return tf.chunked_ce(cfg, x, head, labels, loss_chunk)


def prefill(cfg: ModelConfig, params, batch, *, gather: Gather = None):
    tokens = batch["tokens"]
    x, emb = tf._embed(cfg, params, tokens, gather)
    x, cache = _run(cfg, params, x, mode="prefill", gather=gather)
    x = base.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = tf._head(cfg, params, emb, gather)
    cache["pos"] = jnp.int32(tokens.shape[1])
    return x[:, -1:] @ head, cache


def decode_step(cfg: ModelConfig, params, token, cache, *,
                gather: Gather = None):
    x, emb = tf._embed(cfg, params, token, gather)
    layer_caches = {k: v for k, v in cache.items() if k != "pos"}
    x, nc = _run(cfg, params, x, mode="decode", cache=layer_caches,
                 pos=cache["pos"], gather=gather)
    x = base.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = tf._head(cfg, params, emb, gather)
    nc["pos"] = cache["pos"] + token.shape[1]
    return x @ head, nc


def init_cache(cfg: ModelConfig, batch_size: int, max_seq: int,
               dtype=None) -> dict:
    dtype = dtype or cfg.dtype
    ngroups, _ = _groups(cfg)
    zl = mamba2._zero_layer_cache(cfg, batch_size)
    mcache = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape).copy(), zl)
    kv, hd = cfg.n_kv_heads, cfg.hd
    acache = {"k": jnp.zeros((ngroups, batch_size, max_seq, kv, hd), dtype),
              "v": jnp.zeros((ngroups, batch_size, max_seq, kv, hd), dtype)}
    return {"mamba": mcache, "attn": acache, "pos": jnp.int32(max_seq - 1)}
