"""Decoder-only transformer LM covering 8 of the 10 assigned archs.

Variants selected by ``ModelConfig`` flags:
  * dense GQA/MQA (tinyllama-1.1b, granite-20b)
  * local/global alternating + softcaps + sandwich norms (gemma2-2b/27b)
  * MoE ffn (qwen3-moe-235b)
  * MLA attention + MoE + first-dense-layer (deepseek-v2-lite)
  * interleaved gated cross-attention to vision embeds (llama-3.2-vision)

Layer loop is ``lax.scan`` over stacked params (pairs for local/global,
groups of ``cross_attn_every`` self layers + 1 cross layer for the VLM);
each scan body is ``jax.remat``-ed.  ``gather`` (optional) is the FSDP
param-streaming hook: it receives each sliced layer dict and all-gathers
the FSDP-sharded leaves through the Flare collectives (``repro.train``).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.models import base
from repro.models.base import ModelConfig

Gather = Callable | None


# ---------------------------------------------------------------------------
# Parameter construction.
# ---------------------------------------------------------------------------

def _attn_params(cfg: ModelConfig, key, scale):
    h, kv, hd, d = cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_model
    ks = jax.random.split(key, 4)
    p = {
        "wq": base.dense_init(ks[0], (d, h * hd), scale),
        "wk": base.dense_init(ks[1], (d, kv * hd), scale),
        "wv": base.dense_init(ks[2], (d, kv * hd), scale),
        "wo": base.dense_init(ks[3], (h * hd, d), scale),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,))
        p["k_norm"] = jnp.zeros((hd,))
    return p


def _mla_params(cfg: ModelConfig, key, scale):
    d, h = cfg.d_model, cfg.n_heads
    qk = cfg.mla_qk_nope + cfg.mla_qk_rope
    ks = jax.random.split(key, 5)
    return {
        "wq": base.dense_init(ks[0], (d, h * qk), scale),
        "w_dkv": base.dense_init(ks[1], (d, cfg.mla_kv_lora), scale),
        "w_kr": base.dense_init(ks[2], (d, cfg.mla_qk_rope), scale),
        "w_ukv": base.dense_init(
            ks[3], (cfg.mla_kv_lora, h * (cfg.mla_qk_nope + cfg.mla_v_dim)),
            cfg.mla_kv_lora ** -0.5),
        "wo": base.dense_init(ks[4], (h * cfg.mla_v_dim, d), scale),
        "kv_norm": jnp.zeros((cfg.mla_kv_lora,)),
    }


def _mlp_params(cfg: ModelConfig, key, scale, d_ff=None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "w_gate": base.dense_init(ks[0], (d, f), scale),
        "w_up": base.dense_init(ks[1], (d, f), scale),
        "w_down": base.dense_init(ks[2], (f, d), f ** -0.5),
    }


def _moe_params(cfg: ModelConfig, key, scale):
    d, f, e, held = cfg.d_model, cfg.moe_d_ff, cfg.n_experts, cfg.n_held
    ks = jax.random.split(key, 5)
    p = {
        "router": base.dense_init(ks[0], (d, e), scale),
        "w_gate": base.dense_init(ks[1], (held, d, f), scale),
        "w_up": base.dense_init(ks[2], (held, d, f), scale),
        "w_down": base.dense_init(ks[3], (held, f, d), f ** -0.5),
    }
    if cfg.n_shared_experts:
        p["shared"] = _mlp_params(cfg, ks[4], scale,
                                  d_ff=cfg.moe_d_ff * cfg.n_shared_experts)
    return p


def _layer_params(cfg: ModelConfig, key, *, moe: bool, mla: bool = False):
    ks = jax.random.split(key, 2)
    scale = cfg.d_model ** -0.5
    attn = _mla_params(cfg, ks[0], scale) if mla \
        else _attn_params(cfg, ks[0], scale)
    ffn = _moe_params(cfg, ks[1], scale) if moe \
        else _mlp_params(cfg, ks[1], scale)
    p = {"ln1": jnp.zeros((cfg.d_model,)), "attn": attn,
         "ln2": jnp.zeros((cfg.d_model,)), "ffn": ffn}
    if cfg.post_norms:
        p["ln1b"] = jnp.zeros((cfg.d_model,))
        p["ln2b"] = jnp.zeros((cfg.d_model,))
    return p


def _cross_params(cfg: ModelConfig, key):
    p = _layer_params(cfg, key, moe=False)
    p["gate_attn"] = jnp.zeros((1,))
    p["gate_mlp"] = jnp.zeros((1,))
    p["q_norm"] = jnp.zeros((cfg.hd,))
    p["k_norm"] = jnp.zeros((cfg.hd,))
    return p


def _stack(keys, make):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *[make(k) for k in keys])


def init_params(cfg: ModelConfig, key) -> dict:
    keys = jax.random.split(key, 8)
    params: dict = {
        "embed": base.dense_init(keys[0], (cfg.vocab, cfg.d_model), 0.02),
        "final_norm": jnp.zeros((cfg.d_model,)),
    }
    if not getattr(cfg, "tie_embeddings", False):
        params["lm_head"] = base.dense_init(
            keys[1], (cfg.d_model, cfg.vocab), cfg.d_model ** -0.5)

    moe, mla = cfg.is_moe, cfg.mla_kv_lora > 0
    n = cfg.n_layers
    if cfg.cross_attn_every > 0:
        # VLM: groups of (cross_attn_every − 1) self layers + 1 cross layer
        g = cfg.cross_attn_every
        ngroups = n // g
        nself = ngroups * (g - 1)
        lk = jax.random.split(keys[2], nself)
        ck = jax.random.split(keys[3], ngroups)
        params["layers"] = _stack(lk, lambda k: _layer_params(cfg, k, moe=False))
        params["cross_layers"] = _stack(ck, lambda k: _cross_params(cfg, k))
    elif cfg.local_global:
        pairs = n // 2
        lk = jax.random.split(keys[2], pairs)
        gk = jax.random.split(keys[3], pairs)
        params["local_layers"] = _stack(
            lk, lambda k: _layer_params(cfg, k, moe=moe))
        params["global_layers"] = _stack(
            gk, lambda k: _layer_params(cfg, k, moe=moe))
    elif cfg.first_dense_layers > 0:
        # deepseek's leading dense layers (wider ffn), then the MoE ones:
        # two stacks, both under the root ``layers``
        dk = jax.random.split(keys[2], cfg.first_dense_layers)
        mk = jax.random.split(keys[3], n - cfg.first_dense_layers)
        params["layers"] = {
            "dense": _stack(dk, lambda k: _layer_params(cfg, k, moe=False,
                                                        mla=mla)),
            "moe": _stack(mk, lambda k: _layer_params(cfg, k, moe=moe,
                                                      mla=mla))}
    else:
        lk = jax.random.split(keys[2], n)
        params["layers"] = _stack(
            lk, lambda k: _layer_params(cfg, k, moe=moe, mla=mla))
    return params


# ---------------------------------------------------------------------------
# Layer application.
# ---------------------------------------------------------------------------

def _self_layer(cfg: ModelConfig, lp: dict, x, *, window=0, cache=None,
                pos_offset=None, moe: bool):
    h = base.rmsnorm(x, lp["ln1"], cfg.norm_eps)
    attn_out, newkv = base.gqa_attention(cfg, lp["attn"], h, window=window,
                                         cache=cache, pos_offset=pos_offset)
    attn_out = base.tag_block_out(cfg, attn_out)
    if cfg.post_norms:
        attn_out = base.rmsnorm(attn_out, lp["ln1b"], cfg.norm_eps)
    x = x + attn_out
    h = base.rmsnorm(x, lp["ln2"], cfg.norm_eps)
    ffn_out, stats = _ffn(cfg, lp["ffn"], h, moe)
    ffn_out = base.tag_block_out(cfg, ffn_out)
    if cfg.post_norms:
        ffn_out = base.rmsnorm(ffn_out, lp["ln2b"], cfg.norm_eps)
    return x + ffn_out, newkv, stats


def _ffn(cfg: ModelConfig, p: dict, h, moe: bool):
    """The feed-forward block and its MoE stats (empty unless dropless)."""
    if moe and cfg.dropless:
        return base.moe_dropless(cfg, p, h)
    if moe:
        return base.moe_block(cfg, p, h), {}
    with jax.named_scope("lm.mlp"):
        return base.swiglu(p, h), {}


def _mla_layer(cfg: ModelConfig, lp: dict, x, *, cache=None,
               pos_offset=None, moe: bool):
    """Deepseek MLA block: low-rank compressed KV + decoupled rope key."""
    h = base.rmsnorm(x, lp["ln1"], cfg.norm_eps)
    with jax.named_scope("lm.mla"):
        out, newkv = _mla_attention(cfg, lp["attn"], h, cache=cache,
                                    pos_offset=pos_offset)
    x = x + base.tag_block_out(cfg, out)

    h = base.rmsnorm(x, lp["ln2"], cfg.norm_eps)
    ffn_out, stats = _ffn(cfg, lp["ffn"], h, moe)
    return x + base.tag_block_out(cfg, ffn_out), newkv, stats


def _mla_attention(cfg: ModelConfig, ap: dict, h, *, cache=None,
                   pos_offset=None):
    """MLA: queries, the compressed KV latent with its RMSNorm and the
    shared rope key; RoPE or YaRN on the rope parts;
    attention (absorbed in the latent space for a cached decode when
    ``mla_absorbed``).  Returns (out (B,S,D), (c_kv, k_rope))."""
    b, s, _ = h.shape
    nope, rope, vd = cfg.mla_qk_nope, cfg.mla_qk_rope, cfg.mla_v_dim
    nh = cfg.n_heads

    q = (h @ ap["wq"]).reshape(b, s, nh, nope + rope)
    c_kv = h @ ap["w_dkv"]                         # (B,S,kv_lora)
    c_kv = base.rmsnorm(c_kv, ap["kv_norm"], cfg.norm_eps)
    k_r = (h @ ap["w_kr"]).reshape(b, s, 1, rope)  # shared rope key

    pos0 = pos_offset if pos_offset is not None else jnp.int32(0)
    pos = pos0 + jnp.arange(s)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    freqs = base.rope_of(cfg, rope)
    q_rope = base.apply_rope(q_rope, pos, cfg.rope_theta, freqs=freqs)
    k_r = base.apply_rope(k_r, pos, cfg.rope_theta, freqs=freqs)
    scale = (nope + rope) ** -0.5 * base.softmax_mscale(cfg)

    if cache is not None:
        c_kv = jax.lax.dynamic_update_slice_in_dim(
            cache["c_kv"], c_kv, cache["pos"], axis=1)
        k_r = jax.lax.dynamic_update_slice_in_dim(
            cache["k_rope"], k_r, cache["pos"], axis=1)
        kv_len = cache["pos"] + s
        q_pos = cache["pos"] + jnp.arange(s)
    else:
        kv_len = None
        q_pos = None

    sk = c_kv.shape[1]
    if cfg.mla_absorbed and cache is not None:
        # absorbed MLA (beyond-paper, EXPERIMENTS.md §Perf cell 4): attend
        # in the latent space — never re-expand K/V from the compressed
        # cache.  Score = q_nope·(c_kv·W_uk)ᵀ = (q_nope·W_ukᵀ)·c_kvᵀ, and
        # the attention output stays latent until one small up-projection.
        lora = cfg.mla_kv_lora
        w_ukv = ap["w_ukv"].reshape(lora, nh, nope + vd)
        w_uk, w_uv = w_ukv[..., :nope], w_ukv[..., nope:]
        q_lat = jnp.einsum("bshn,lhn->bshl", q_nope, w_uk)   # (B,s,H,lora)
        scores = jnp.einsum("bshl,btl->bhst",
                            q_lat.astype(jnp.float32),
                            c_kv.astype(jnp.float32))
        scores = scores + jnp.einsum(
            "bshr,btqr->bhst", q_rope.astype(jnp.float32),
            k_r.astype(jnp.float32))
        scores = scores * scale
        kpos = jnp.arange(sk)
        mask = (kpos[None, :] <= q_pos[:, None]) & (kpos[None, :] < kv_len)
        scores = jnp.where(mask[None, None], scores, -1e30)
        p_attn = jax.nn.softmax(scores, axis=-1)
        o_lat = jnp.einsum("bhst,btl->bshl", p_attn,
                           c_kv.astype(jnp.float32))         # (B,s,H,lora)
        out = jnp.einsum("bshl,lhv->bshv", o_lat.astype(cfg.dtype), w_uv)
    else:
        ukv = (c_kv @ ap["w_ukv"]).reshape(b, sk, nh, nope + vd)
        k_nope, v = ukv[..., :nope], ukv[..., nope:]
        k = jnp.concatenate([k_nope,
                             jnp.broadcast_to(k_r, (b, sk, nh, rope))],
                            axis=-1)
        qq = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = base.attend(qq, k, v, causal=True, q_pos=q_pos, kv_len=kv_len,
                          scale=scale,
                          chunk=cfg.attn_chunk if cache is None else 0)
    return out.reshape(b, s, nh * vd) @ ap["wo"], (c_kv, k_r)


def _cross_layer(cfg: ModelConfig, lp: dict, x, vision_kv):
    """Gated cross-attention layer (llama-3.2-vision style)."""
    h = base.rmsnorm(x, lp["ln1"], cfg.norm_eps)
    b, s, _ = x.shape
    nh, hd = cfg.n_heads, cfg.hd
    ap = lp["attn"]
    q = (h @ ap["wq"]).reshape(b, s, nh, hd)
    q = base.rmsnorm(q, lp["q_norm"], cfg.norm_eps)
    k, v = vision_kv
    out = base.attend(q, k, v, causal=False)
    out = out.reshape(b, s, nh * hd) @ ap["wo"]
    x = x + jnp.tanh(lp["gate_attn"]) * out
    h = base.rmsnorm(x, lp["ln2"], cfg.norm_eps)
    x = x + jnp.tanh(lp["gate_mlp"]) * base.swiglu(lp["ffn"], h)
    return x


def cross_kv(cfg: ModelConfig, lp: dict, vision_embeds):
    """Precompute cross-attention K/V from (gathered) cross-layer params."""
    b, t, _ = vision_embeds.shape
    kv, hd = cfg.n_kv_heads, cfg.hd
    ap = lp["attn"]
    k = (vision_embeds @ ap["wk"]).reshape(b, t, kv, hd)
    k = base.rmsnorm(k, lp["k_norm"], cfg.norm_eps)
    v = (vision_embeds @ ap["wv"]).reshape(b, t, kv, hd)
    return k, v


# ---------------------------------------------------------------------------
# Stacks: train/prefill/decode drivers.
# ---------------------------------------------------------------------------

def _g(gather: Gather, lp: dict) -> dict:
    return gather(lp) if gather is not None else lp


def run_stack(cfg: ModelConfig, params: dict, x, *, mode: str,
              cache: dict | None = None, pos: jax.Array | None = None,
              vision_embeds=None, gather: Gather = None):
    """Run all layers. mode ∈ {train, prefill, decode}.

    Returns (x, new_cache_pytree_or_None, stats).  Cache layout per
    stack: ``{"k": (L,B,S,KV,hd), "v": ...}`` (or MLA/cross variants),
    plus ``pos`` managed by the caller.  ``stats`` sums the dropless MoE
    layers' stats (``base.moe_dropless``) over the layers; empty for
    every other model.
    """
    moe, mla = cfg.is_moe, cfg.mla_kv_lora > 0
    want_cache = mode in ("prefill", "decode")

    def mk_body(layer_fn):
        def body(carry, xs):
            x = carry
            lp, layer_cache = xs
            lp = _g(gather, lp)
            c = None
            if mode == "decode":
                c = dict(layer_cache)
                c["pos"] = pos
            out, newkv, stats = layer_fn(x, lp, c)
            ys = None
            if want_cache:
                ys = _cache_entry(newkv, mla)
            return out, (ys, stats)
        return body

    def _cache_entry(newkv, is_mla):
        if is_mla:
            return {"c_kv": newkv[0], "k_rope": newkv[1]}
        return {"k": newkv[0], "v": newkv[1]}

    def scan_layers(x, stack, layer_fn, cache_stack):
        body = mk_body(layer_fn)
        if mode == "train":
            body = base.remat(cfg, body)
        xs = (stack, cache_stack if cache_stack is not None
              else _null_cache(stack))
        x, (ys, stats) = jax.lax.scan(body, x, xs)
        return x, ys, jax.tree.map(lambda a: jnp.sum(a, 0), stats)

    def _null_cache(stack):
        # scan requires a pytree with matching leading dim; use per-layer None
        n = jax.tree.leaves(stack)[0].shape[0]
        return jnp.zeros((n, 0))

    new_cache: dict = {}

    if cfg.cross_attn_every > 0:
        g = cfg.cross_attn_every
        ngroups = cfg.n_layers // g
        # reshape self stack (ngroups*(g-1), ...) → (ngroups, g-1, ...)
        self_stack = jax.tree.map(
            lambda a: a.reshape((ngroups, g - 1) + a.shape[1:]),
            params["layers"])
        cross_stack = params["cross_layers"]

        if mode == "decode":
            # cross KV is static during decode and comes from the prefill
            # cache; self-attn caches are consumed/updated via nested scan.
            sc = jax.tree.map(
                lambda a: a.reshape((ngroups, g - 1) + a.shape[1:]),
                cache["self"])
            cross_cache = cache["cross"]

            def group_body(carry, xs):
                x = carry
                gstack, gcache, ckv, cstack = xs

                def inner(xc, xs2):
                    lp, lcache = xs2
                    lp = _g(gather, lp)
                    c = dict(lcache); c["pos"] = pos
                    out, newkv, _ = _self_layer(cfg, lp, xc, moe=False,
                                                cache=c, pos_offset=pos)
                    return out, _cache_entry(newkv, False)
                x, ys = jax.lax.scan(inner, x, (gstack, gcache))
                cp = _g(gather, cstack)
                x = _cross_layer(cfg, cp, x, (ckv["k"], ckv["v"]))
                return x, ys

            x, ys = jax.lax.scan(group_body, x,
                                 (self_stack, sc, cross_cache, cross_stack))
            new_self = jax.tree.map(
                lambda a: a.reshape((-1,) + a.shape[2:]), ys)
            return x, {"self": new_self, "cross": cross_cache}, {}

        x, ys = jax.lax.scan(
            _vlm_group_body(cfg, gather, mode, want_cache, vision_embeds,
                            pos, moe),
            x, (self_stack, cross_stack, _null_cache(self_stack)))
        if want_cache:
            self_c, cross_c = ys
            self_c = jax.tree.map(
                lambda a: a.reshape((-1,) + a.shape[2:]), self_c)
            return x, {"self": self_c, "cross": cross_c}, {}
        return x, None, {}

    if cfg.local_global:
        def pair_body(carry, xs):
            x = carry
            lp_l, lp_g, cache_l, cache_g = xs
            cl = cg = None
            if mode == "decode":
                cl = dict(cache_l); cl["pos"] = pos
                cg = dict(cache_g); cg["pos"] = pos
            po = pos if mode != "train" else None
            x, kv_l, _ = _self_layer(cfg, _g(gather, lp_l), x, moe=moe,
                                     window=cfg.window, cache=cl,
                                     pos_offset=po)
            x, kv_g, _ = _self_layer(cfg, _g(gather, lp_g), x, moe=moe,
                                     cache=cg, pos_offset=po)
            ys = None
            if want_cache:
                ys = (_cache_entry(kv_l, False), _cache_entry(kv_g, False))
            return x, ys
        body = base.remat(cfg, pair_body) if mode == "train" else pair_body
        nc_l = cache["local"] if mode == "decode" else \
            _null_cache(params["local_layers"])
        nc_g = cache["global"] if mode == "decode" else \
            _null_cache(params["global_layers"])
        x, ys = jax.lax.scan(body, x, (params["local_layers"],
                                       params["global_layers"], nc_l, nc_g))
        if want_cache:
            return x, {"local": ys[0], "global": ys[1]}, {}
        return x, None, {}

    layer_fn_moe = moe
    def plain_fn(x, lp, c):
        po = pos if mode != "train" else None
        if mla:
            return _mla_layer(cfg, lp, x, cache=c, pos_offset=po,
                              moe=layer_fn_moe)
        return _self_layer(cfg, lp, x, cache=c, pos_offset=po,
                           moe=layer_fn_moe)

    if cfg.first_dense_layers > 0:
        def dense_fn(x, lp, c):
            po = pos if mode != "train" else None
            if mla:
                return _mla_layer(cfg, lp, x, cache=c, pos_offset=po,
                                  moe=False)
            return _self_layer(cfg, lp, x, cache=c, pos_offset=po, moe=False)
        dc = cache["dense"] if mode == "decode" else None
        x, ys_d, _ = scan_layers(x, params["layers"]["dense"], dense_fn, dc)
        mc = cache["moe"] if mode == "decode" else None
        x, ys_m, stats = scan_layers(x, params["layers"]["moe"], plain_fn,
                                     mc)
        if want_cache:
            return x, {"dense": ys_d, "moe": ys_m}, stats
        return x, None, stats

    lc = cache["layers"] if mode == "decode" else None
    x, ys, stats = scan_layers(x, params["layers"], plain_fn, lc)
    if want_cache:
        return x, {"layers": ys}, stats
    return x, None, stats


def _vlm_group_body(cfg, gather, mode, want_cache, vision_embeds, pos, moe):
    g = cfg.cross_attn_every

    def body(carry, xs):
        x = carry
        gstack, cstack, _ = xs

        def inner(xc, lp):
            lp = _g(gather, lp)
            out, newkv, _ = _self_layer(cfg, lp, xc, moe=False)
            ys = {"k": newkv[0], "v": newkv[1]} if want_cache else None
            return out, ys
        if mode == "train":
            inner = base.remat(cfg, inner)
        x, ys = jax.lax.scan(inner, x, gstack)
        cp = _g(gather, cstack)
        kv = cross_kv(cfg, cp, vision_embeds)
        x = _cross_layer(cfg, cp, x, kv)
        cys = {"k": kv[0], "v": kv[1]} if want_cache else None
        return x, (ys, cys)
    return body


# ---------------------------------------------------------------------------
# Public entry points.
# ---------------------------------------------------------------------------

def _embed(cfg: ModelConfig, params, tokens, gather: Gather):
    emb = params["embed"]
    if gather is not None:
        emb = gather({"embed": emb})["embed"]
    x = emb.astype(cfg.dtype)[tokens]
    return x, emb


def _head(cfg: ModelConfig, params, emb, gather: Gather):
    if "lm_head" in params:
        head = params["lm_head"]
        if gather is not None:
            head = gather({"lm_head": head})["lm_head"]
        return head.astype(cfg.dtype)
    return emb.T.astype(cfg.dtype)


def loss_fn(cfg: ModelConfig, params: dict, batch: dict, *,
            gather: Gather = None, loss_chunk: int = 2048) -> jax.Array:
    return loss_and_aux(cfg, params, batch, gather=gather,
                        loss_chunk=loss_chunk)[0]


def loss_and_aux(cfg: ModelConfig, params: dict, batch: dict, *,
                 gather: Gather = None, loss_chunk: int = 2048):
    """(loss, counters): the cross-entropy plus the dropless MoE layers'
    balance loss; ``counters["moe_rows"]`` the rows routed to the held
    experts, summed over layers (dropless MoE only, else no counters)."""
    tokens, labels = batch["tokens"], batch["labels"]
    with jax.named_scope("lm.embed"):
        x, emb = _embed(cfg, params, tokens, gather)
    x, _, stats = run_stack(cfg, params, x, mode="train",
                            vision_embeds=batch.get("vision_embeds"),
                            gather=gather)
    with jax.named_scope("lm.head_loss"):
        x = base.rmsnorm(x, params["final_norm"], cfg.norm_eps)
        head = _head(cfg, params, emb, gather)
        loss = chunked_ce(cfg, x, head, labels, loss_chunk)
    if "rows" not in stats:
        return loss, {}
    return loss + stats["aux"], {"moe_rows": stats["rows"]}


def chunked_ce(cfg, x, head, labels, chunk):
    """Sequence-chunked cross-entropy: avoids a (B,S,V) live tensor."""
    b, s, d = x.shape
    chunk = min(chunk, s)
    if s % chunk:
        chunk = s
    nc = s // chunk
    xc = x.reshape(b, nc, chunk, d).swapaxes(0, 1)        # (nc,B,chunk,D)
    lc = labels.reshape(b, nc, chunk).swapaxes(0, 1)

    def body(tot, xs):
        xx, ll = xs
        logits = xx @ head
        return tot + base.cross_entropy(logits, ll, cfg.logit_softcap) * (
            1.0 / nc), None
    tot, _ = jax.lax.scan(body, jnp.float32(0), (xc, lc))
    return tot


def prefill(cfg: ModelConfig, params: dict, batch: dict, *,
            gather: Gather = None):
    """Forward pass over a prompt; returns (last-token logits, cache)."""
    tokens = batch["tokens"]
    x, emb = _embed(cfg, params, tokens, gather)
    x, cache, _ = run_stack(cfg, params, x, mode="prefill",
                            vision_embeds=batch.get("vision_embeds"),
                            gather=gather)
    x = base.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = _head(cfg, params, emb, gather)
    logits = x[:, -1:] @ head
    logits = base.softcap(logits, cfg.logit_softcap)
    cache["pos"] = jnp.int32(tokens.shape[1])
    return logits, cache


def decode_step(cfg: ModelConfig, params: dict, token, cache: dict, *,
                gather: Gather = None):
    """One decode step: token (B,1) + cache → (logits, updated cache)."""
    pos = cache["pos"]
    x, emb = _embed(cfg, params, token, gather)
    layer_caches = {k: v for k, v in cache.items() if k != "pos"}
    x, new_cache, _ = run_stack(cfg, params, x, mode="decode",
                                cache=layer_caches, pos=pos, gather=gather)
    x = base.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = _head(cfg, params, emb, gather)
    logits = base.softcap(x @ head, cfg.logit_softcap)
    new_cache["pos"] = pos + token.shape[1]
    return logits, new_cache


def init_cache(cfg: ModelConfig, batch_size: int, max_seq: int,
               dtype=None) -> dict:
    """Zero KV cache sized for ``max_seq`` (decode dry-run shapes)."""
    dtype = dtype or cfg.dtype
    kv, hd = cfg.n_kv_heads, cfg.hd
    mla = cfg.mla_kv_lora > 0

    def kv_entry(n_layers):
        if mla:
            return {"c_kv": jnp.zeros((n_layers, batch_size, max_seq,
                                       cfg.mla_kv_lora), dtype),
                    "k_rope": jnp.zeros((n_layers, batch_size, max_seq, 1,
                                         cfg.mla_qk_rope), dtype)}
        return {"k": jnp.zeros((n_layers, batch_size, max_seq, kv, hd), dtype),
                "v": jnp.zeros((n_layers, batch_size, max_seq, kv, hd), dtype)}

    if cfg.cross_attn_every > 0:
        g = cfg.cross_attn_every
        ngroups = cfg.n_layers // g
        nself = ngroups * (g - 1)
        return {"self": kv_entry(nself),
                "cross": {"k": jnp.zeros((ngroups, batch_size,
                                          cfg.vision_tokens, kv, hd), dtype),
                          "v": jnp.zeros((ngroups, batch_size,
                                          cfg.vision_tokens, kv, hd), dtype)},
                "pos": jnp.int32(max_seq - 1)}
    if cfg.local_global:
        pairs = cfg.n_layers // 2
        return {"local": kv_entry(pairs), "global": kv_entry(pairs),
                "pos": jnp.int32(max_seq - 1)}
    if cfg.first_dense_layers > 0:
        return {"dense": kv_entry(cfg.first_dense_layers),
                "moe": kv_entry(cfg.n_layers - cfg.first_dense_layers),
                "pos": jnp.int32(max_seq - 1)}
    return {"layers": kv_entry(cfg.n_layers), "pos": jnp.int32(max_seq - 1)}
