"""Shared model config + layer library (pure-functional JAX).

Everything is a function of ``(cfg, params, inputs)``; parameters live in
plain dict pytrees with per-layer leaves stacked on a leading ``L`` axis
so the layer loop is a single ``lax.scan`` (compact HLO, fast compiles,
remat-friendly — essential for the 100-layer dry-run configs).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.ad_checkpoint
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """One config covers all ten assigned architectures (unused fields 0)."""

    name: str
    family: str                    # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0              # 0 → d_model // n_heads

    # --- MoE ---------------------------------------------------------------
    n_experts: int = 0
    experts_per_token: int = 0
    moe_d_ff: int = 0
    n_shared_experts: int = 0
    first_dense_layers: int = 0
    capacity_factor: float = 1.25  # 0 → dropless over the experts held
    experts_held: int = 0          # experts [0, held) live here; 0 → all
    norm_topk_prob: bool = True    # renormalise the top-k gates (dropless)
    aux_loss_alpha: float = 0.0    # sequence-wise balance loss weight

    # --- MLA (deepseek) ------------------------------------------------------
    mla_kv_lora: int = 0
    mla_qk_nope: int = 128
    mla_qk_rope: int = 64
    mla_v_dim: int = 128

    # --- gemma2 --------------------------------------------------------------
    local_global: bool = False     # alternate local(window)/global layers
    window: int = 4096
    attn_softcap: float = 0.0
    logit_softcap: float = 0.0
    post_norms: bool = False       # gemma2 sandwich norms

    # --- attention extras ------------------------------------------------------
    qk_norm: bool = False          # qwen3 per-head q/k RMSNorm
    rope_theta: float = 1e4
    # YaRN (arXiv:2309.00071); factor 0 → plain RoPE.  ``yarn_mscale`` is
    # the source's ``mscale_all_dim``: the softmax scale takes
    # mscale(factor, yarn_mscale)²; cos and sin stay unscaled, as where
    # the source's ``mscale`` equals it (DeepSeek-V2)
    yarn_factor: float = 0.0
    yarn_original: int = 4096
    yarn_mscale: float = 0.0

    # --- SSM (mamba2) ----------------------------------------------------------
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_headdim: int = 64
    ssm_chunk: int = 256
    ssm_conv: int = 4

    # --- hybrid (zamba2) ---------------------------------------------------------
    hybrid_attn_every: int = 0     # shared attn block after every N ssm layers

    # --- VLM (llama-3.2-vision) -----------------------------------------------
    cross_attn_every: int = 0      # one cross-attn layer per N self layers
    vision_tokens: int = 0

    # --- audio (whisper) ---------------------------------------------------------
    encoder_layers: int = 0
    encoder_tokens: int = 0
    max_positions: int = 32768     # learned-pos-emb table size (whisper)

    # --- head tying ----------------------------------------------------------------
    tie_embeddings: bool = False

    # --- numerics -----------------------------------------------------------------
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16      # computation dtype (params stay fp32)

    # --- performance knobs (hillclimb levers; defaults = paper-faithful
    # baseline, see EXPERIMENTS.md §Perf) -----------------------------------
    attn_chunk: int = 0            # >0 → chunked online-softmax attention
    moe_chunk: int = 0             # >0 → dropless MoE tokens per pass
    moe_combine: str = "gather"    # gather | scatter_ar (EP combine path)
    remat_policy: str = "full"     # full | dots | names
    mla_absorbed: bool = False     # decode attends in the latent space

    @property
    def hd(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // self.n_heads if self.n_heads else 0

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_headdim

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def dropless(self) -> bool:
        return self.is_moe and self.capacity_factor == 0

    @property
    def n_held(self) -> int:
        """Routed experts whose weights this program holds."""
        return self.experts_held or self.n_experts

    def scaled(self, **overrides) -> "ModelConfig":
        """Reduced config for CPU smoke tests (same family/topology)."""
        return dataclasses.replace(self, **overrides)


# ---------------------------------------------------------------------------
# Normalization / positional encodings.
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


def layernorm(x: jax.Array, w: jax.Array, b: jax.Array,
              eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


def rope_freqs(hd: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


#: YaRN's bounds of the frequency ramp, in turns over the original context
#: (the paper's and DeepSeek-V2's beta_fast and beta_slow)
YARN_BETA_FAST, YARN_BETA_SLOW = 32.0, 1.0


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor ``0.1·m·ln s + 1`` (1 for
    s ≤ 1)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_freqs(hd: int, theta: float, factor: float,
               original: int) -> jax.Array:
    """YaRN's "NTK-by-parts" frequencies (arXiv:2309.00071 §3.2, as the
    DeepSeek-V2 modeling code computes them): dimensions that turn more
    than ``YARN_BETA_FAST`` times over the original context keep their
    frequency, those that turn fewer than ``YARN_BETA_SLOW`` times are
    divided by ``factor``, and a linear ramp blends the ones between."""
    def dim_of(turns):
        return hd * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    lo = max(math.floor(dim_of(YARN_BETA_FAST)), 0)
    hi = min(math.ceil(dim_of(YARN_BETA_SLOW)), hd - 1)
    hi = hi + 0.001 if hi == lo else hi
    ramp = jnp.clip((jnp.arange(hd // 2, dtype=jnp.float32) - lo) / (hi - lo),
                    0.0, 1.0)
    base = rope_freqs(hd, theta)
    return base / factor * ramp + base * (1.0 - ramp)


def rope_of(cfg: "ModelConfig", hd: int) -> jax.Array:
    """The frequencies of ``cfg``'s rotary embedding: YaRN's where it
    sets a factor."""
    if cfg.yarn_factor <= 0:
        return rope_freqs(hd, cfg.rope_theta)
    return yarn_freqs(hd, cfg.rope_theta, cfg.yarn_factor, cfg.yarn_original)


def softmax_mscale(cfg: "ModelConfig") -> float:
    """YaRN's factor on the softmax scale: ``mscale(s, yarn_mscale)²``."""
    if cfg.yarn_factor <= 0 or not cfg.yarn_mscale:
        return 1.0
    return yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale) ** 2


def apply_rope(x: jax.Array, pos: jax.Array, theta: float, *,
               freqs: jax.Array | None = None) -> jax.Array:
    """x: (..., S, H, hd); pos: (..., S) absolute positions.  ``freqs``
    (hd/2,) replaces the plain ``theta`` frequencies (YaRN)."""
    hd = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(hd, theta)                   # (hd/2,)
    ang = pos[..., None].astype(jnp.float32) * freqs    # (..., S, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., None, :]                             # broadcast over heads
    sin = sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    return jnp.tanh(x / cap) * cap if cap > 0 else x


def remat(cfg: ModelConfig, fn):
    """Layer-boundary remat with the configured policy.

    ``full`` recomputes everything in the backward pass — minimal memory,
    but the recompute repeats the TP collectives.  ``dots`` saves every
    matmul output (including S×S attention scores — measured to blow the
    memory term up; kept for the record).  ``names`` saves only the
    tensors tagged ``block_out`` — the attention/ffn block outputs that
    sit right after the TP all-reduces, so backward replays neither the
    collectives nor the projections, at one activation-sized save per
    block (EXPERIMENTS.md §Perf iteration 2).
    """
    if cfg.remat_policy == "dots":
        return jax.remat(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    if cfg.remat_policy == "names":
        return jax.remat(
            fn, policy=jax.checkpoint_policies.save_only_these_names(
                "block_out"))
    return jax.remat(fn)


def tag_block_out(cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Mark a tensor as a named remat checkpoint (remat_policy="names")."""
    if cfg.remat_policy == "names":
        return jax.ad_checkpoint.checkpoint_name(x, "block_out")
    return x


# ---------------------------------------------------------------------------
# Attention (GQA / MQA, sliding-window, softcap, KV-cache, cross-attn).
# ---------------------------------------------------------------------------

def attend(q: jax.Array, k: jax.Array, v: jax.Array, *,
           causal: bool, q_pos: jax.Array | None = None,
           kv_len: jax.Array | None = None,
           window: int = 0, attn_cap: float = 0.0,
           scale: float | None = None, chunk: int = 0) -> jax.Array:
    """Scaled dot-product attention.

    q: (B, Sq, H, hd); k/v: (B, Sk, KV, hd) with H % KV == 0.
    ``q_pos``: absolute positions of the queries (for causal masking of
    cached decode).  ``kv_len``: number of valid cache entries.
    ``chunk``: >0 → online-softmax over KV chunks (flash-attention
    schedule): the (Sq, Sk) score matrix is never materialized in HBM —
    the memory-roofline lever for the long-sequence cells.
    """
    if chunk > 0 and q.shape[1] > 1 and k.shape[1] % chunk == 0 \
            and kv_len is None:
        return _attend_chunked(q, k, v, causal=causal, window=window,
                               attn_cap=attn_cap, scale=scale, chunk=chunk)
    b, sq, h, hd = q.shape
    sk, kv = k.shape[1], k.shape[2]
    g = h // kv
    scale = scale if scale is not None else hd ** -0.5
    qf = (q * scale).astype(jnp.float32).reshape(b, sq, kv, g, hd)
    kf = k.astype(jnp.float32)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qf, kf)    # (B,KV,G,Sq,Sk)
    scores = softcap(scores, attn_cap)

    kpos = jnp.arange(sk)
    mask = jnp.ones((sq, sk), bool)
    if causal:
        qp = q_pos if q_pos is not None else jnp.arange(sq)
        mask &= kpos[None, :] <= qp[:, None]
        if window > 0:
            mask &= kpos[None, :] > qp[:, None] - window
    if kv_len is not None:
        mask &= kpos[None, :] < kv_len
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(b, sq, h, v.shape[-1]).astype(q.dtype)


def _attend_chunked(q, k, v, *, causal, window, attn_cap, scale, chunk):
    """Online-softmax attention tiled over BOTH queries and keys.

    Outer scan over query chunks, inner scan over KV chunks carrying a
    *query-chunk-sized* (m, l, o) state — the flash-attention schedule.
    HBM traffic per pass drops from O(S²) (materialized scores +
    softmax intermediates) to O(S²·vd/chunk) carry writes + O(S²/chunk)
    KV reloads; with chunk ≫ vd that is a ≥8× cut on the memory term
    (EXPERIMENTS.md §Perf iteration 2 — iteration 1's KV-only tiling was
    refuted: its carry was full-output-sized).  Both scan bodies are
    rematerialised, so the backward pass keeps one query chunk's (m, l,
    o) carries and never a chunk of scores per step: no O(S²) residual.
    """
    b, sq, h, hd = q.shape
    sk, kv = k.shape[1], k.shape[2]
    g = h // kv
    vd = v.shape[-1]
    scale = scale if scale is not None else hd ** -0.5
    nq = max(1, sq // chunk)
    qc_len = sq // nq
    nk = sk // chunk

    qf = (q * scale).astype(jnp.float32).reshape(b, nq, qc_len, kv, g, hd)
    qf = jnp.moveaxis(qf, 1, 0)                       # (NQ,B,qc,KV,G,hd)
    kc = jnp.moveaxis(k.astype(jnp.float32)
                      .reshape(b, nk, chunk, kv, hd), 1, 0)
    vc = jnp.moveaxis(v.astype(jnp.float32)
                      .reshape(b, nk, chunk, kv, vd), 1, 0)

    @jax.checkpoint
    def q_body(_, xs):
        qi, qb = xs                                   # (B,qc,KV,G,hd)
        qpos = qi * qc_len + jnp.arange(qc_len)

        @jax.checkpoint
        def kv_body(carry, xs2):
            m, l, o = carry
            ki, kb, vb = xs2
            s = jnp.einsum("bqkgd,bckd->bkgqc", qb, kb)  # (B,KV,G,qc,C)
            s = softcap(s, attn_cap)
            kpos = ki * chunk + jnp.arange(chunk)
            mask = jnp.ones((qc_len, chunk), bool)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
                if window > 0:
                    mask &= kpos[None, :] > qpos[:, None] - window
            s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l = l * alpha + p.sum(-1)
            o = o * alpha[..., None] \
                + jnp.einsum("bkgqc,bckd->bkgqd", p, vb)
            return (m_new, l, o), None

        m0 = jnp.full((b, kv, g, qc_len), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, kv, g, qc_len), jnp.float32)
        o0 = jnp.zeros((b, kv, g, qc_len, vd), jnp.float32)
        (m, l, o), _ = jax.lax.scan(kv_body, (m0, l0, o0),
                                    (jnp.arange(nk), kc, vc))
        out = o / jnp.maximum(l[..., None], 1e-30)    # (B,KV,G,qc,vd)
        return None, jnp.moveaxis(out, 3, 1)          # (B,qc,KV,G,vd)

    _, outs = jax.lax.scan(q_body, None, (jnp.arange(nq), qf))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, sq, h, vd)
    return out.astype(q.dtype)


def gqa_attention(cfg: ModelConfig, p: dict, x: jax.Array, *,
                  causal: bool = True, window: int = 0,
                  pos_offset: jax.Array | None = None,
                  cache: dict | None = None,
                  kv_override: tuple | None = None) -> tuple:
    """Full attention block: qkv proj + rope + attend + out proj.

    Returns (out, new_cache_kv) where new_cache_kv is (k, v) for cache
    construction (prefill) or the updated (k, v) (decode).  ``cache`` is
    ``{"k": (B,Smax,KV,hd), "v": ..., "pos": scalar}`` for decode.
    ``kv_override`` supplies precomputed (k, v) for cross-attention.
    """
    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = (x @ p["wq"]).reshape(b, s, h, hd)
    if kv_override is None:
        kk = (x @ p["wk"]).reshape(b, s, kv, hd)
        vv = (x @ p["wv"]).reshape(b, s, kv, hd)
    else:
        kk, vv = kv_override
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        if kv_override is None:
            kk = rmsnorm(kk, p["k_norm"], cfg.norm_eps)

    if kv_override is None:
        pos0 = pos_offset if pos_offset is not None else jnp.int32(0)
        pos = pos0 + jnp.arange(s)
        q = apply_rope(q, pos, cfg.rope_theta)
        kk = apply_rope(kk, pos, cfg.rope_theta)

    if cache is not None:
        kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], kk, cache["pos"],
                                                 axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], vv, cache["pos"],
                                                 axis=1)
        out = attend(q, kc, vc, causal=True,
                     q_pos=cache["pos"] + jnp.arange(s),
                     kv_len=cache["pos"] + s, window=window,
                     attn_cap=cfg.attn_softcap)
        newkv = (kc, vc)
    else:
        out = attend(q, kk, vv, causal=causal and kv_override is None,
                     window=window, attn_cap=cfg.attn_softcap,
                     chunk=cfg.attn_chunk)
        newkv = (kk, vv)
    out = out.reshape(b, s, h * hd) @ p["wo"]
    return out, newkv


# ---------------------------------------------------------------------------
# Feed-forward: SwiGLU / GELU MLPs and the MoE block.
# ---------------------------------------------------------------------------

def swiglu(p: dict, x: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def gelu_mlp(p: dict, x: jax.Array) -> jax.Array:
    return jax.nn.gelu(x @ p["w_up"] + p["b_up"]) @ p["w_down"] + p["b_down"]


def moe_block(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    """Capacity-based top-k MoE with scatter dispatch / gather combine.

    experts weights: ``w_gate/w_up``: (E, D, F), ``w_down``: (E, F, D),
    router: (D, E).  Experts are sharded over the ``model`` axis (EP).
    Tokens are scattered into per-expert capacity slots (positions from a
    cumulative count — collision-free by construction) and gathered back
    weighted by the gate; the expert matmuls themselves are dense batched
    einsums on the MXU.  O(T·k·D) routing work — the (T,E,C) one-hot
    einsum dispatch would cost as much as the experts themselves at
    train-scale T.
    """
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.experts_per_token
    # capacity: cf-scaled balanced load, with a floor so small (decode)
    # batches stay effectively dropless
    cap = max(int(cfg.capacity_factor * t * k / e), min(t * k, 32))

    xt = x.reshape(t, d)
    logits = (xt @ p["router"]).astype(jnp.float32)        # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)          # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, -1, keepdims=True), 1e-9)       # renormalize

    # position of each (token, choice) within its expert's capacity
    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.int32)  # (T, k, E)
    pos_in_e = (jnp.cumsum(onehot.reshape(t * k, e), 0)
                .reshape(t, k, e) - 1)
    pos = jnp.sum(pos_in_e * onehot, -1)                   # (T, k)
    keep = pos < cap                                       # drop overflow

    flat_e = gate_idx.reshape(-1)                          # (T·k,)
    flat_c = jnp.clip(pos, 0, cap - 1).reshape(-1)
    keep_f = keep.reshape(-1)

    # dispatch: scatter each kept (token, choice) into its expert slot
    src = jnp.broadcast_to(xt[:, None, :], (t, k, d)).reshape(t * k, d)
    src = jnp.where(keep_f[:, None], src, 0).astype(cfg.dtype)
    w = jnp.where(keep, gate_vals, 0.0).astype(cfg.dtype)  # (T, k)

    if cfg.moe_combine == "scatter_ar":
        # slot → flat-row map (unique by construction); dropped choices
        # write out-of-bounds so they cannot clobber a kept slot
        flat_c_kept = jnp.where(keep_f, flat_c, cap)
        slot_to_row = jnp.full((e, cap), t * k, jnp.int32)
        rows = jnp.arange(t * k, dtype=jnp.int32)
        slot_to_row = slot_to_row.at[flat_e, flat_c_kept].min(
            rows, mode="drop")
        xin = _ep_dispatch(src, flat_e, flat_c, slot_to_row, e, cap, t * k)
    else:
        xin = jnp.zeros((e, cap, d), cfg.dtype)
        xin = xin.at[flat_e, flat_c].add(src, mode="drop",
                                         unique_indices=True)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xin, p["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", xin, p["w_up"])
    out_e = jnp.einsum("ecf,efd->ecd", h, p["w_down"])     # (E, C, D)

    if cfg.moe_combine == "scatter_ar":
        # combine by scattering from the expert-sharded side: each model
        # rank adds its local experts' slots into a replicated (T,D)
        # buffer — a local scatter + one (T,D) all-reduce instead of
        # all-gathering the (E,C,D) buffer.  The buffer stays in the
        # compute dtype so the implicit all-reduce moves bf16, not fp32
        # (≤ k accumulands per row — standard bf16-reduction trade;
        # EXPERIMENTS.md §Perf iterations 1–2).
        slot_gate = jnp.zeros((e, cap), cfg.dtype)
        slot_gate = slot_gate.at[flat_e, flat_c_kept].add(
            w.reshape(-1), mode="drop")
        tok_of_slot = slot_to_row // k                      # (E, C); OOB = t
        out = jnp.zeros((t + 1, d), cfg.dtype)
        out = out.at[tok_of_slot.reshape(-1)].add(
            (out_e * slot_gate[..., None]).reshape(-1, d),
            mode="drop")
        out = out[:t]
    else:
        # combine: gather each choice's slot, weight by its gate value
        gath = out_e[flat_e, flat_c]                       # (T·k, D)
        out = jnp.sum(gath.reshape(t, k, d) * w[..., None], axis=1)

    if cfg.n_shared_experts > 0:
        out = out + swiglu(p["shared"], xt)
    return out.reshape(b, s, d)


def moe_dropless(cfg: ModelConfig, p: dict, x: jax.Array):
    """Top-k MoE without dropped tokens, over the experts held here.

    The router scores all ``n_experts`` (softmax, greedy top-k, gates
    renormalised only if ``norm_topk_prob``); the layer computes the
    part of the result that its held experts ``[0, n_held)`` give, for
    exactly the (token, choice) rows routed to them.  Those rows are sorted by expert and run as one grouped
    product per weight (``grouped_matmul``), whose work follows the
    group sizes; the rows routed to experts held elsewhere sort last and
    come back as zeros.  With ``moe_chunk`` the tokens go through that
    in chunks of ``moe_chunk``, each sorted on its own and recomputed in
    backward, so one chunk's (token, choice) rows are live at a time.
    Shared experts add their output for every token.

    Weights: ``router`` (D, E) float32; ``w_gate``/``w_up`` (held, D, F),
    ``w_down`` (held, F, D); ``shared`` a SwiGLU.  Returns ``(out,
    stats)``: ``stats["aux"]`` is the sequence-wise balance loss
    (DeepSeek-V2 §2.2.3, over all experts, averaged over the batch),
    ``stats["rows"]`` the rows routed to the held experts.
    """
    b, s, d = x.shape
    t = b * s
    e, k, held = cfg.n_experts, cfg.experts_per_token, cfg.n_held
    xt = x.reshape(t, d)

    with jax.named_scope("lm.moe.route"):
        logits = jnp.dot(xt.astype(jnp.float32),
                         p["router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)               # (T, E)
        gate, idx = jax.lax.top_k(probs, k)                   # (T, k)
        if cfg.norm_topk_prob:
            gate = gate / jnp.sum(gate, -1, keepdims=True)
        gate = gate.astype(cfg.dtype)
        aux = _seq_balance_loss(cfg, probs, idx, b, s)

    xt = xt.astype(cfg.dtype)
    chunk = cfg.moe_chunk if 0 < cfg.moe_chunk < t and t % cfg.moe_chunk \
        == 0 else t
    if chunk == t:
        out, rows = _held_experts(cfg, p, xt, gate, idx)
    else:
        def body(_, xs):
            return None, _held_experts(cfg, p, *xs)
        split = lambda a: a.reshape((t // chunk, chunk) + a.shape[1:])
        _, (out, rows) = jax.lax.scan(jax.checkpoint(body), None,
                                      (split(xt), split(gate), split(idx)))
        out, rows = out.reshape(t, d), jnp.sum(rows)
    stats = {"aux": aux, "rows": rows}

    if cfg.n_shared_experts > 0:
        with jax.named_scope("lm.moe.shared"):
            out = out + swiglu(p["shared"], xt)
    return out.reshape(b, s, d), stats


def _held_experts(cfg: ModelConfig, p: dict, xt, gate, idx):
    """The held experts' part of the output for tokens ``xt`` (T, D)
    with their top-k ``gate`` and ``idx`` (T, k), and the rows routed to
    them."""
    t, d = xt.shape
    k, held = idx.shape[1], cfg.n_held
    with jax.named_scope("lm.moe.route"):
        # rows sorted by expert; choices of experts held elsewhere last
        expert = jnp.minimum(idx.reshape(-1), held)           # (T·k,)
        order = jnp.argsort(expert, stable=True)
        inv = jnp.argsort(order)
        sizes = jnp.bincount(expert, length=held + 1).astype(jnp.int32)

    with jax.named_scope("lm.moe.dispatch"):
        xs = _take_rows(xt, order // k, inv, k)

    with jax.named_scope("lm.moe.experts"):
        h = jax.nn.silu(grouped_matmul(xs, p["w_gate"], sizes, cfg.dtype))
        h = h * grouped_matmul(xs, p["w_up"], sizes, cfg.dtype)
        ys = grouped_matmul(h, p["w_down"], sizes, cfg.dtype)

    with jax.named_scope("lm.moe.combine"):
        yc = _take_rows(ys, inv, order, 1).reshape(t, k, d)
        out = jnp.einsum("tkd,tk->td", yc, gate)
    return out, jnp.sum(sizes[:held])


def _seq_balance_loss(cfg: ModelConfig, probs, idx, b: int, s: int):
    """``α · Σᵢ fᵢ·Pᵢ`` per sequence, averaged: ``fᵢ`` the share of the
    sequence's k·S choices that went to expert i, times E/k; ``Pᵢ`` its
    mean router probability over the sequence."""
    if cfg.aux_loss_alpha <= 0:
        return jnp.float32(0)
    e, k = cfg.n_experts, cfg.experts_per_token
    counts = jnp.sum(jax.nn.one_hot(idx.reshape(b, s * k), e,
                                    dtype=jnp.float32), 1)   # (B, E)
    f = counts * (e / (s * k))
    pm = jnp.mean(probs.reshape(b, s, e), 1)
    return cfg.aux_loss_alpha * jnp.mean(jnp.sum(f * pm, -1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take_rows(x, idx, back, fold):
    """``x[idx]`` where ``idx`` visits every row of ``x`` ``fold`` times
    (a permutation, up to copies).  Its transpose is the gather
    ``Σ_fold ḡ[back]``, with ``back`` the inverse permutation: no
    scatter in either pass."""
    return x[idx]


def _take_rows_fwd(x, idx, back, fold):
    return x[idx], back


def _take_rows_bwd(fold, back, g):
    gx = g[back]
    if fold > 1:
        gx = jnp.sum(gx.reshape(-1, fold, g.shape[-1]), 1)
    return gx, None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def grouped_matmul(x: jax.Array, w: jax.Array, sizes: jax.Array,
                   dtype) -> jax.Array:
    """Rows of ``x`` (M, K), sorted into groups, times their group's
    weight ``w`` (G, K, N).  ``sizes`` (G + 1,) counts the rows of each
    group, and last the rows past them, which come out as zeros and are
    not computed.

    On a TPU this is megablox's grouped product (a Pallas kernel whose
    grid follows the group sizes, with its own backward); elsewhere
    ``jax.lax.ragged_dot``."""
    from repro.kernels import ops
    w = w.astype(dtype)
    if not ops._on_tpu():
        return jax.lax.ragged_dot(x, w, sizes[:-1],
                                  preferred_element_type=dtype)
    from jax.experimental.pallas.ops.tpu.megablox import ops as mb
    m, kk = x.shape
    n = w.shape[-1]
    tm = next(t for t in (256, 128, 64, 32, 16, 8) if m % t == 0)
    return mb.gmm(x, w, sizes, dtype, (tm, min(512, kk), min(512, n)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _ep_dispatch(src, flat_e, flat_c, slot_to_row, e, cap, t_k):
    """Token → expert-slot scatter whose *backward* is also a scatter.

    Forward: rows of ``src`` (replicated over the model axis) scatter
    into the expert-sharded (E, C, D) buffer — each expert shard keeps
    only its rows: no communication.  The autodiff transpose would be a
    gather *from* the sharded buffer (→ XLA all-gathers it); instead the
    custom backward scatters grad rows from the sharded side into a
    replicated (T·k, D) buffer via ``slot_to_row`` — local scatter + one
    all-reduce.
    """
    d = src.shape[-1]
    xin = jnp.zeros((e, cap, d), src.dtype)
    return xin.at[flat_e, flat_c].add(src, mode="drop", unique_indices=True)


def _ep_dispatch_fwd(src, flat_e, flat_c, slot_to_row, e, cap, t_k):
    return _ep_dispatch(src, flat_e, flat_c, slot_to_row, e, cap, t_k), \
        slot_to_row


def _ep_dispatch_bwd(e, cap, t_k, slot_to_row, g):
    d = g.shape[-1]
    gsrc = jnp.zeros((t_k + 1, d), jnp.float32)
    gsrc = gsrc.at[slot_to_row.reshape(-1)].add(
        g.reshape(-1, d).astype(jnp.float32), mode="drop")
    return (gsrc[:t_k].astype(g.dtype), None, None, None)


_ep_dispatch.defvjp(_ep_dispatch_fwd, _ep_dispatch_bwd)


# ---------------------------------------------------------------------------
# Initializers.
# ---------------------------------------------------------------------------

def dense_init(key, shape, scale=None, dtype=jnp.float32):
    scale = scale if scale is not None else shape[-2] ** -0.5 \
        if len(shape) >= 2 else 0.02
    return jax.random.normal(key, shape, dtype) * scale


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  logit_cap: float = 0.0) -> jax.Array:
    logits = softcap(logits.astype(jnp.float32), logit_cap)
    lp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(lp, labels[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)
