"""Mamba-2 (SSD, arXiv:2405.21060) — attention-free LM.

The SSD layer is computed with the chunked *state-space duality*
algorithm: intra-chunk interactions are batched matmuls (MXU work —
this is why SSD maps well to TPU), inter-chunk state is a short
``lax.scan`` over chunk boundaries (O(S/chunk) sequential steps).  Decode
keeps O(1) state per layer: a conv window and the (H, P, N) SSM state —
the reason the ``long_500k`` cell runs for this family.

Projections are stored per-component (wz/wx/wb/wc/wdt) rather than as
the fused ``in_proj`` of the reference implementation: the fused layout
concatenates z/x/B/C/dt on one axis, which cannot be tensor-parallel
sharded without splits crossing shard boundaries.  Per-component weights
let the head dimension shard cleanly over the ``model`` axis (every SSD
einsum carries ``h``), with B/C/dt replicated (tiny).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.models import base
from repro.models.base import ModelConfig

Gather = Callable | None


def _chunk_cumsum(x: jax.Array) -> jax.Array:
    """(..., Q) → (..., Q): out[..., q] = Σ_{k≤q} x[..., k] in float32.

    A product with an upper-triangular matrix of ones, on the MXU.
    ``HIGHEST`` keeps the float32 arithmetic of a cumsum (one bf16 pass
    would round each log-decay before ``exp``); the backward is the
    product with the transpose, at the same precision.
    """
    q = x.shape[-1]
    tri = jnp.triu(jnp.ones((q, q), jnp.float32))
    return jnp.einsum("...k,kq->...q", x, tri,
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def segsum(cs: jax.Array) -> jax.Array:
    """(..., Q) → (..., Q, Q): out[i,j] = Σ_{k=j+1..i} x[k] (−inf above diag).

    ``cs`` is x's prefix sum, ``_chunk_cumsum(x)``: a product, since a TPU
    runs ``jnp.cumsum`` as a ``reduce-window`` over lanes; the caller's
    one prefix sum serves both this and the decays.
    """
    q = cs.shape[-1]
    d = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), bool))
    return jnp.where(mask, d, -jnp.inf)


def ssd_chunked(xdt: jax.Array, a_bar: jax.Array, bb: jax.Array,
                cc: jax.Array, chunk: int, h0: jax.Array):
    """Chunked SSD scan.

    The within-chunk prefix sum of the log-decay is one product with a
    triangular matrix (``_chunk_cumsum``): a TPU runs ``jnp.cumsum`` as a
    ``reduce-window`` over lanes.

    xdt: (B,S,H,P) inputs pre-multiplied by dt;  a_bar: (B,S,H) log-decay;
    bb/cc: (B,S,N);  h0: (B,H,P,N) initial state.
    Returns (y: (B,S,H,P), h_final).
    """
    b, s, h, p = xdt.shape
    n = bb.shape[-1]
    if s % chunk:
        raise ValueError(f"seq {s} % chunk {chunk} != 0")
    c = s // chunk
    x = xdt.reshape(b, c, chunk, h, p)
    ab = a_bar.reshape(b, c, chunk, h).transpose(0, 3, 1, 2)   # (B,H,C,Q)
    bbc = bb.reshape(b, c, chunk, n)
    ccc = cc.reshape(b, c, chunk, n)

    acum = _chunk_cumsum(ab)                                    # (B,H,C,Q)
    # 1) intra-chunk (the "attention-like" quadratic-in-chunk term)
    ll = jnp.exp(segsum(acum))                                  # (B,H,C,Q,Q)
    scores = jnp.einsum("bcqn,bckn->bcqk", ccc, bbc)
    w = scores[:, None] * ll                                    # (B,H,C,Q,Q)
    y_diag = jnp.einsum("bhcqk,bckhp->bcqhp", w, x)

    # 2) per-chunk end states
    decay_to_end = jnp.exp(acum[..., -1:] - acum)               # (B,H,C,Q)
    states = jnp.einsum("bckn,bhck,bckhp->bchpn", bbc, decay_to_end, x)

    # 3) inter-chunk recurrence over c
    chunk_decay = jnp.exp(acum[..., -1])                        # (B,H,C)

    def scan_body(hprev, xs):
        st, dec = xs                                            # (B,H,P,N),(B,H)
        hnext = hprev * dec[..., None, None] + st
        return hnext, hprev
    states_c = states.transpose(1, 0, 2, 3, 4)                  # (C,B,H,P,N)
    decay_c = chunk_decay.transpose(2, 0, 1)                    # (C,B,H)
    h_final, prev_states = jax.lax.scan(scan_body, h0, (states_c, decay_c))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)          # (B,C,H,P,N)

    # 4) inter-chunk output
    state_decay = jnp.exp(acum)                                 # (B,H,C,Q)
    y_off = jnp.einsum("bcqn,bchpn,bhcq->bcqhp", ccc, prev_states,
                       state_decay)
    y = (y_diag + y_off).reshape(b, s, h, p)
    return y, h_final


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 state: jax.Array | None):
    """Depthwise causal conv, width w.shape[0]; state = last W−1 inputs."""
    bsz, s, _ = x.shape
    width = w.shape[0]
    if state is None:
        pad = jnp.zeros((bsz, width - 1, x.shape[-1]), x.dtype)
        xp = jnp.concatenate([pad, x], 1)
    else:
        xp = jnp.concatenate([state, x], 1)
    out = sum(xp[:, i:i + s] * w[i] for i in range(width))
    return jax.nn.silu(out + b), xp[:, -(width - 1):]


def mamba_block(cfg: ModelConfig, p: dict, x: jax.Array, *,
                cache: dict | None = None):
    """One Mamba-2 mixer.  cache = {"conv_x","conv_b","conv_c","ssm"}."""
    b, s, di = x.shape[0], x.shape[1], cfg.d_inner

    with jax.named_scope("mamba2.in_proj"):
        z = x @ p["wz"]                                  # (B,S,di)
        xin = x @ p["wx"]                                # (B,S,di)
        bb = x @ p["wb"]                                 # (B,S,N)
        cc = x @ p["wc"]                                 # (B,S,N)
        dt = x @ p["wdt"]                                # (B,S,H)

    cx = cache["conv_x"] if cache is not None else None
    cb = cache["conv_b"] if cache is not None else None
    ccv = cache["conv_c"] if cache is not None else None
    with jax.named_scope("mamba2.conv"):
        xin, ncx = _causal_conv(xin, p["conv_xw"], p["conv_xb"], cx)
        bb, ncb = _causal_conv(bb, p["conv_bw"], p["conv_bb"], cb)
        cc, ncc = _causal_conv(cc, p["conv_cw"], p["conv_cb"], ccv)

    y, h_final = _ssd(cfg, p, xin, bb, cc, dt, cache)
    with jax.named_scope("mamba2.out"):
        y = y.reshape(b, s, di).astype(cfg.dtype if x.dtype != jnp.float32
                                       else jnp.float32)
        y = base.rmsnorm(y * jax.nn.silu(z), p["gate_norm"], cfg.norm_eps)
        out = y @ p["out_proj"]
    new_cache = None
    if cache is not None:
        new_cache = {"conv_x": ncx, "conv_b": ncb, "conv_c": ncc,
                     "ssm": h_final}
    return out, new_cache


@jax.named_scope("mamba2.ssd")
def _ssd(cfg: ModelConfig, p: dict, xin, bb, cc, dt, cache: dict | None):
    """The SSM over the conv outputs: the chunked SSD scan (or the
    recurrence for decode and odd lengths) and the D skip; returns
    (y: (B,S,H,P) float32, final state)."""
    b, s, _ = xin.shape
    n, h, pd = cfg.ssm_state, cfg.ssm_heads, cfg.ssm_headdim
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))    # (B,S,H)
    a = -jnp.exp(p["A_log"].astype(jnp.float32))                # (H,)
    a_bar = dt * a                                              # log decay
    xh = xin.reshape(b, s, h, pd)
    xdt = xh.astype(jnp.float32) * dt[..., None]

    h0 = cache["ssm"] if cache is not None else \
        jnp.zeros((b, h, pd, n), jnp.float32)
    if s % cfg.ssm_chunk == 0 and s > 1:
        y, h_final = ssd_chunked(xdt, a_bar, bb.astype(jnp.float32),
                                 cc.astype(jnp.float32), cfg.ssm_chunk, h0)
    else:
        # recurrent path (decode / odd lengths): step the SSM directly
        def step(hprev, xs):
            xt, at, bt, ct = xs                  # (B,H,P),(B,H),(B,N),(B,N)
            hnext = hprev * jnp.exp(at)[..., None, None] \
                + xt[..., None] * bt[:, None, None, :]
            yt = jnp.einsum("bhpn,bn->bhp", hnext, ct)
            return hnext, yt
        xs = (xdt.transpose(1, 0, 2, 3), a_bar.transpose(1, 0, 2),
              bb.astype(jnp.float32).transpose(1, 0, 2),
              cc.astype(jnp.float32).transpose(1, 0, 2))
        h_final, ys = jax.lax.scan(step, h0, xs)
        y = ys.transpose(1, 0, 2, 3)             # (B,S,H,P)

    y = y + xh.astype(jnp.float32) * p["D"][None, None, :, None]
    return y, h_final


def _layer_params(cfg: ModelConfig, key):
    d, di, n, h = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    w = cfg.ssm_conv
    ks = jax.random.split(key, 6)
    return {
        "ln": jnp.zeros((d,)),
        "wz": base.dense_init(ks[0], (d, di)),
        "wx": base.dense_init(ks[1], (d, di)),
        "wb": base.dense_init(ks[2], (d, n)),
        "wc": base.dense_init(ks[3], (d, n)),
        "wdt": base.dense_init(ks[4], (d, h)),
        "conv_xw": base.dense_init(ks[5], (w, di), 0.2),
        "conv_xb": jnp.zeros((di,)),
        "conv_bw": base.dense_init(ks[5], (w, n), 0.2),
        "conv_bb": jnp.zeros((n,)),
        "conv_cw": base.dense_init(ks[5], (w, n), 0.2),
        "conv_cb": jnp.zeros((n,)),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h)),
        "D": jnp.ones((h,)),
        "dt_bias": jnp.zeros((h,)),
        "gate_norm": jnp.zeros((di,)),
        "out_proj": base.dense_init(ks[5], (di, d)),
    }


def init_params(cfg: ModelConfig, key) -> dict:
    ks = jax.random.split(key, 3)
    lk = jax.random.split(ks[0], cfg.n_layers)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                           *[_layer_params(cfg, k) for k in lk])
    return {
        "embed": base.dense_init(ks[1], (cfg.vocab, cfg.d_model), 0.02),
        "layers": stacked,
        "final_norm": jnp.zeros((cfg.d_model,)),
        "lm_head": base.dense_init(ks[2], (cfg.d_model, cfg.vocab),
                                   cfg.d_model ** -0.5),
    }


def _g(gather: Gather, lp):
    return gather(lp) if gather is not None else lp


def _zero_layer_cache(cfg: ModelConfig, b: int):
    w = cfg.ssm_conv - 1
    return {"conv_x": jnp.zeros((b, w, cfg.d_inner), cfg.dtype),
            "conv_b": jnp.zeros((b, w, cfg.ssm_state), cfg.dtype),
            "conv_c": jnp.zeros((b, w, cfg.ssm_state), cfg.dtype),
            "ssm": jnp.zeros((b, cfg.ssm_heads, cfg.ssm_headdim,
                              cfg.ssm_state), jnp.float32)}


def _run(cfg: ModelConfig, params, x, *, mode: str, cache=None,
         gather: Gather = None):
    want_cache = mode in ("prefill", "decode")

    def body(carry, xs):
        x = carry
        lp, lcache = xs
        lp = _g(gather, lp)
        c = lcache if mode == "decode" else (
            _zero_layer_cache(cfg, x.shape[0]) if mode == "prefill" else None)
        h = base.rmsnorm(x, lp["ln"], cfg.norm_eps)
        out, nc = mamba_block(cfg, lp, h, cache=c)
        out = base.tag_block_out(cfg, out)
        return x + out, (nc if want_cache else None)

    if mode == "train":
        body = base.remat(cfg, body)
    xs_cache = cache["layers"] if mode == "decode" \
        else jnp.zeros((cfg.n_layers, 0))
    with jax.named_scope("mamba2.layer"):
        x, ys = jax.lax.scan(body, x, (params["layers"], xs_cache))
    return x, ({"layers": ys} if want_cache else None)


def loss_fn(cfg: ModelConfig, params, batch, *, gather: Gather = None,
            loss_chunk: int = 2048):
    from repro.models import transformer as tf
    tokens, labels = batch["tokens"], batch["labels"]
    with jax.named_scope("lm.embed"):
        x, emb = tf._embed(cfg, params, tokens, gather)
    x, _ = _run(cfg, params, x, mode="train", gather=gather)
    with jax.named_scope("lm.head_loss"):
        x = base.rmsnorm(x, params["final_norm"], cfg.norm_eps)
        head = tf._head(cfg, params, emb, gather)
        return tf.chunked_ce(cfg, x, head, labels, loss_chunk)


def prefill(cfg: ModelConfig, params, batch, *, gather: Gather = None):
    from repro.models import transformer as tf
    tokens = batch["tokens"]
    x, emb = tf._embed(cfg, params, tokens, gather)
    x, cache = _run(cfg, params, x, mode="prefill", gather=gather)
    x = base.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = tf._head(cfg, params, emb, gather)
    cache["pos"] = jnp.int32(tokens.shape[1])
    return x[:, -1:] @ head, cache


def decode_step(cfg: ModelConfig, params, token, cache, *,
                gather: Gather = None):
    from repro.models import transformer as tf
    x, emb = tf._embed(cfg, params, token, gather)
    layer_caches = {k: v for k, v in cache.items() if k != "pos"}
    x, nc = _run(cfg, params, x, mode="decode", cache=layer_caches,
                 gather=gather)
    x = base.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = tf._head(cfg, params, emb, gather)
    nc["pos"] = cache["pos"] + token.shape[1]
    return x @ head, nc


def init_cache(cfg: ModelConfig, batch_size: int, max_seq: int,
               dtype=None) -> dict:
    """SSM decode state is O(1) in sequence length — max_seq unused."""
    del max_seq
    zl = _zero_layer_cache(cfg, batch_size)
    return {"layers": jax.tree.map(
        lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape).copy(), zl),
        "pos": jnp.int32(0)}
