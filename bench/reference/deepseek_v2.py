"""Plain float32 reference of DeepSeek-V2(-Lite) (arXiv:2405.04434).

Written from the paper and the source's published configuration, in
``jax.numpy`` at ``Precision.HIGHEST``, with no kernel, cache, sorting
or sharding, and nothing imported from the program.

- Multi-head latent attention: queries from the hidden state (no query
  compression), a compressed KV latent with its RMSNorm, per-head keys
  and values from the latent, one rope key shared by the heads; YaRN
  rotary frequencies (arXiv:2309.00071, β_fast 32, β_slow 1) on the
  rope dimensions and the softmax scale ``(nope + rope)^-½ ·
  mscale(s, mscale_all_dim)²`` (``yarn_mscale``); cos and sin unscaled,
  the source's ``mscale`` being equal to ``mscale_all_dim``.
  Attention runs in blocks of ``QBLOCK`` queries, so a row of 8192
  positions never holds its whole score matrix.
- Mixture of experts: a dense softmax router over all experts, greedy
  top-k, gates renormalised only if ``norm_topk_prob``; a loop over the
  experts held here, each applied to every token and weighted by the
  token's gate for it (zero where it was not chosen); the shared
  experts on every token.  Choices of experts not held add nothing.
  The sequence-wise balance loss ``α·Σᵢ fᵢ·Pᵢ`` of every MoE layer is
  added to the loss.
- Untied head, mean next-token cross-entropy.

It follows the configuration as it is run (the benchmark file's
``program``), where that departs from the source: the rope dimensions
rotated as two halves, norm weights as ``1 + w``.  ``capacity_factor``
above 0 plants a fault: each expert keeps only its first
``capacity_factor · S · k / E`` choices of a row, in token order.

``dot`` is every matrix product the model makes.  The control passes one
that rounds both operands to a lower precision first.
"""
from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
#: queries per attention block
QBLOCK = 512

Dot = Callable[..., jax.Array]


def exact_dot(spec: str, *xs: jax.Array) -> jax.Array:
    return jnp.einsum(spec, *xs, precision=HIGHEST)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def silu(x):
    return x * jax.nn.sigmoid(x)


# -- YaRN ----------------------------------------------------------------

def yarn_get_mscale(scale: float, mscale: float) -> float:
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(cfg: dict, dim: int) -> jax.Array:
    """Inverse frequencies of the ``dim`` rope dimensions: interpolated
    (divided by the factor) below ``beta_slow`` turns over the original
    context, kept above ``beta_fast``, a linear ramp between."""
    base, factor = cfg["rope_theta"], cfg["yarn_factor"]
    orig = cfg["yarn_original"]
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if factor <= 0:
        return extra

    def correction_dim(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(correction_dim(32)), 0)           # beta_fast
    high = min(math.ceil(correction_dim(1)), dim - 1)       # beta_slow
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                      # 1: extrapolate, 0: interpolate
    return extra / factor * (1.0 - keep) + extra * keep


def softmax_mult(cfg: dict) -> float:
    factor, m_all = cfg["yarn_factor"], cfg["yarn_mscale"]
    return yarn_get_mscale(factor, m_all) ** 2 if factor > 0 and m_all \
        else 1.0


def rope(x, inv_freq):
    """Rotate the last axis of x (..., S, [H,] R) as two halves by the
    angle position · inv_freq; positions 0, 1, ... on axis 1."""
    s = x.shape[1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if x.ndim == 4:
        cos, sin = cos[:, None, :], sin[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# -- layers --------------------------------------------------------------

def attention(cfg: dict, p: dict, x, dot: Dot):
    """Causal MLA over x (B, S, D)."""
    bsz, s, _ = x.shape
    h, nope, rd, vd = (cfg["n_heads"], cfg["mla_qk_nope"], cfg["mla_qk_rope"],
                       cfg["mla_v_dim"])
    q = dot("bsd,de->bse", x, p["wq"]).reshape(bsz, s, h, nope + rd)
    latent = rmsnorm(dot("bsd,dl->bsl", x, p["w_dkv"]), p["kv_norm"],
                     cfg["norm_eps"])
    kv = dot("bsl,le->bse", latent, p["w_ukv"]).reshape(bsz, s, h, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    inv_freq = yarn_inv_freq(cfg, rd)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], inv_freq)
    k_rope = rope(dot("bsd,dr->bsr", x, p["w_kr"]), inv_freq)
    scale = (nope + rd) ** -0.5 * softmax_mult(cfg)

    qb = min(QBLOCK, s)
    nb = s // qb
    kpos = jnp.arange(s)

    def block(_, xs):
        i, qn, qr = xs                          # (B, qb, H, ·)
        sc = dot("bqhd,bkhd->bhqk", qn, k_nope) \
            + dot("bqhr,bkr->bhqk", qr, k_rope)
        qpos = i * qb + jnp.arange(qb)
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc * scale, -jnp.inf)
        return None, dot("bhqk,bkhv->bqhv", jax.nn.softmax(sc, -1), v)

    blocks = lambda a: jnp.moveaxis(a.reshape((bsz, nb, qb) + a.shape[2:]),
                                    1, 0)
    _, o = jax.lax.scan(jax.checkpoint(block), None,
                        (jnp.arange(nb), blocks(q_nope), blocks(q_rope)))
    o = jnp.moveaxis(o, 0, 1).reshape(bsz, s, h * vd)
    return dot("bse,ed->bsd", o, p["wo"])


def mlp(p: dict, x, dot: Dot):
    return dot("tf,fd->td",
               silu(dot("td,df->tf", x, p["w_gate"]))
               * dot("td,df->tf", x, p["w_up"]), p["w_down"])


def moe(cfg: dict, p: dict, x, dot: Dot):
    """(output, balance loss, rows routed to the held experts) of one
    MoE layer over x (B, S, D)."""
    bsz, s, d = x.shape
    e, k = cfg["n_experts"], cfg["experts_per_token"]
    held = cfg.get("experts_held") or e
    xt = x.reshape(bsz * s, d)
    probs = jax.nn.softmax(dot("td,de->te", xt, p["router"]), -1)
    gate, idx = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        gate = gate / jnp.sum(gate, -1, keepdims=True)
    chosen = jax.nn.one_hot(idx, e)                       # (T, k, E)
    cf = cfg.get("capacity_factor", 0.0)
    if cf > 0:                                            # planted fault
        cap = int(cf * s * k / e)
        rows = chosen.reshape(bsz, s * k, e)
        pos = jnp.cumsum(rows, 1) - rows
        chosen = chosen * (pos < cap).reshape(chosen.shape)
    g = jnp.einsum("tke,tk->te", chosen, gate, precision=HIGHEST)   # (T, E)
    out = jnp.zeros_like(xt)
    for j in range(held):
        ex = {n: p[n][j] for n in ("w_gate", "w_up", "w_down")}
        out = out + g[:, j:j + 1] * mlp(ex, xt, dot)
    out = out + mlp(p["shared"], xt, dot)

    counts = jnp.sum(jax.nn.one_hot(idx, e).reshape(bsz, s * k, e), 1)
    f = counts * (e / (s * k))
    pm = jnp.mean(probs.reshape(bsz, s, e), 1)
    aux = cfg["aux_loss_alpha"] * jnp.mean(jnp.sum(f * pm, -1))
    rows = jnp.sum(chosen[..., :held])
    return out.reshape(bsz, s, d), aux, rows


def layer(cfg: dict, p: dict, x, dot: Dot, moe_layer: bool):
    x = x + attention(cfg, p["attn"], rmsnorm(x, p["ln1"], cfg["norm_eps"]),
                      dot)
    h = rmsnorm(x, p["ln2"], cfg["norm_eps"])
    if moe_layer:
        out, aux, rows = moe(cfg, p["ffn"], h, dot)
    else:
        b, s, d = h.shape
        out = mlp(p["ffn"], h.reshape(b * s, d), dot).reshape(b, s, d)
        aux, rows = jnp.float32(0), jnp.float32(0)
    return x + out, aux, rows


def hidden(cfg: dict, params: dict, tokens, dot: Dot = exact_dot):
    """(final-normed hidden states, summed balance loss, rows routed to
    the held experts summed over layers); each layer recomputed in
    backward."""
    x = params["embed"][tokens]
    aux = rows = jnp.float32(0)
    for name, moe_layer in (("dense", False), ("moe", True)):
        def body(carry, lp, moe_layer=moe_layer):
            h, a, r = carry
            h, da, dr = layer(cfg, lp, h, dot, moe_layer)
            return (h, a + da, r + dr), None
        (x, aux, rows), _ = jax.lax.scan(jax.checkpoint(body), (x, aux, rows),
                                         params["layers"][name])
    return rmsnorm(x, params["final_norm"], cfg["norm_eps"]), aux, rows


def cross_entropy(x, head, labels, dot: Dot):
    """Mean next-token cross-entropy, one row of the batch at a time."""
    def row(carry, xs):
        xr, lr = xs
        logits = dot("sd,dv->sv", xr, head)
        lse = jax.nn.logsumexp(logits, -1)
        picked = jnp.take_along_axis(logits, lr[:, None], -1)[:, 0]
        return carry + jnp.sum(lse - picked), None
    tot, _ = jax.lax.scan(jax.checkpoint(row), jnp.float32(0), (x, labels))
    return tot / labels.size


def loss(cfg: dict, params: dict, tokens, labels, dot: Dot = exact_dot):
    x, aux, _ = hidden(cfg, params, tokens, dot)
    return cross_entropy(x, params["lm_head"], labels, dot) + aux
