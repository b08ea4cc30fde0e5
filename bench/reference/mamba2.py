"""Plain float32 reference of the Mamba-2 language model (arXiv:2405.21060).

Written from the paper, in ``jax.numpy`` at ``Precision.HIGHEST``, with
no kernel, cache or sharding, and nothing imported from the program.
The SSD layer is the paper's own minimal listing (``ssd_minimal_discrete``):
blocks of ``BLOCK`` positions, the exact segment sums inside a block,
and the block states carried by a segment-sum decay matrix.

It follows the configuration as it is run (``bench/configs/*.json``),
including where that departs from the paper: separate z/x/B/C/dt
projections (the paper fuses them; the same maps), one B/C group, the
norm weight as ``1 + w``.  The output head is the embedding's transpose
(tied, as in the source) unless the tree holds an ``lm_head``.

``dot`` is every matrix product the model makes.  The control passes one
that rounds both operands to a lower precision first.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
#: SSD block length of the reference (its own choice, not the program's
#: chunk): short blocks keep the in-block cumulative sums small, so the
#: float32 decays lose little to cancellation
BLOCK = 64

Dot = Callable[..., jax.Array]


def exact_dot(spec: str, *xs: jax.Array) -> jax.Array:
    return jnp.einsum(spec, *xs, precision=HIGHEST)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def silu(x):
    return x * jax.nn.sigmoid(x)


def causal_conv(x, w, b):
    """Depthwise causal convolution: x (B, S, C), w (K, C), b (C)."""
    k, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return silu(sum(xp[:, i:i + s] * w[i] for i in range(k)) + b)


def segsum(x):
    """(..., T) → (..., T, T): out[i, j] = x[j+1] + ... + x[i] for i ≥ j,
    −inf above the diagonal; each entry summed on its own (no
    difference of running sums)."""
    t = x.shape[-1]
    xx = jnp.broadcast_to(x[..., :, None], x.shape + (t,))
    xx = jnp.where(jnp.tril(jnp.ones((t, t), bool), -1), xx, 0.0)
    ss = jnp.cumsum(xx, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), ss, -jnp.inf)


def ssd(x, a, b, c, dot: Dot, block: int = BLOCK):
    """y_t = C_t · h_t,  h_t = exp(a_t) h_{t−1} + x_t ⊗ B_t,  h_0 = 0.

    x (B, L, H, P), a (B, L, H), b and c (B, L, N).
    """
    bsz, length, h, p = x.shape
    n = b.shape[-1]
    nc = length // block
    x = x.reshape(bsz, nc, block, h, p)
    a = a.reshape(bsz, nc, block, h).transpose(0, 3, 1, 2)      # b h c l
    b = b.reshape(bsz, nc, block, n)
    c = c.reshape(bsz, nc, block, n)
    a_cs = jnp.cumsum(a, -1)
    decay = jnp.exp(segsum(a))                                  # b h c l s
    y_diag = dot("bcln,bcsn,bhcls,bcshp->bclhp", c, b, decay, x)
    decay_states = jnp.exp(a_cs[..., -1:] - a_cs)               # b h c l
    states = dot("bcln,bhcl,bclhp->bchpn", b, decay_states, x)
    states = jnp.concatenate(
        [jnp.zeros((bsz, 1, h, p, n), states.dtype), states], 1)
    decay_chunk = jnp.exp(segsum(jnp.pad(a_cs[..., -1], ((0, 0), (0, 0),
                                                         (1, 0)))))
    states = dot("bhzc,bchpn->bzhpn", decay_chunk, states)[:, :-1]
    y_off = dot("bcln,bchpn,bhcl->bclhp", c, states, jnp.exp(a_cs))
    return (y_diag + y_off).reshape(bsz, length, h, p)


def mamba_layer(cfg: dict, p: dict, x, dot: Dot):
    """One residual Mamba-2 block: x + out_proj(norm(y · silu(z)))."""
    eps = cfg["norm_eps"]
    heads, hd = cfg["ssm_heads"], cfg["ssm_headdim"]
    bsz, s, _ = x.shape
    u = rmsnorm(x, p["ln"], eps)
    z = dot("bsd,de->bse", u, p["wz"])
    xi = causal_conv(dot("bsd,de->bse", u, p["wx"]), p["conv_xw"],
                     p["conv_xb"])
    bb = causal_conv(dot("bsd,de->bse", u, p["wb"]), p["conv_bw"],
                     p["conv_bb"])
    cc = causal_conv(dot("bsd,de->bse", u, p["wc"]), p["conv_cw"],
                     p["conv_cb"])
    dt = jax.nn.softplus(dot("bsd,de->bse", u, p["wdt"]) + p["dt_bias"])
    a = -jnp.exp(p["A_log"])
    xh = xi.reshape(bsz, s, heads, hd)
    y = ssd(xh * dt[..., None], dt * a, bb, cc, dot)
    y = (y + xh * p["D"][:, None]).reshape(bsz, s, heads * hd)
    y = rmsnorm(y * silu(z), p["gate_norm"], eps)
    return x + dot("bse,ed->bsd", y, p["out_proj"])


def hidden(cfg: dict, params: dict, tokens, dot: Dot):
    """Embedding, then every layer in turn (each recomputed in backward)."""
    x = params["embed"][tokens]
    body = jax.checkpoint(lambda h, lp: (mamba_layer(cfg, lp, h, dot), None))
    x, _ = jax.lax.scan(body, x, params["layers"])
    return rmsnorm(x, params["final_norm"], cfg["norm_eps"])


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
    x = hidden(cfg, params, tokens, dot)
    head = params["lm_head"] if "lm_head" in params else params["embed"].T
    return cross_entropy(x, head, labels, dot)
