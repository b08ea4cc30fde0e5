"""Model FLOPs and the grouped products' work of a DeepSeek-V2-style MoE
model (MLA, a dense first block, routed and shared experts), from the
configuration's ``program`` settings alone.

Counts the matrix products the model requires (2 FLOPs per
multiply-add), leaves out elementwise work and everything recomputed in
backward, and counts training as three forward passes (forward, then
backward through activations and through weights).  Attention scores
are causal: a mean context of (S + 1) / 2 positions.  Routed experts
count at their expected load here: ``k · held / E`` of a token's
choices land on the experts this chip holds.
"""
from __future__ import annotations


def _mlp(d: int, f: int) -> float:
    return 6.0 * d * f                      # gate, up and down products


def mla_per_token(p: dict, seq: int) -> float:
    """One MLA block, forward, per token."""
    d, h = p["d_model"], p["n_heads"]
    nope, rope, vd = p["mla_qk_nope"], p["mla_qk_rope"], p["mla_v_dim"]
    lora = p["mla_kv_lora"]
    proj = 2 * d * (h * (nope + rope) + lora + rope) \
        + 2 * lora * h * (nope + vd) + 2 * h * vd * d
    ctx = (seq + 1) / 2
    return proj + 2 * h * (nope + rope) * ctx + 2 * h * vd * ctx


def held_experts(p: dict) -> int:
    return p.get("experts_held") or p["n_experts"]


def moe_ffn_per_token(p: dict) -> float:
    """One MoE feed-forward block, forward, per token: router, shared
    experts, and the held experts at their expected load."""
    d, e, k = p["d_model"], p["n_experts"], p["experts_per_token"]
    f = p["moe_d_ff"]
    return (2 * d * e + _mlp(d, f * p["n_shared_experts"])
            + _mlp(d, f) * k * held_experts(p) / e)


def forward_per_token(p: dict, seq: int) -> float:
    dense = p["first_dense_layers"]
    mla = mla_per_token(p, seq)
    return (dense * (mla + _mlp(p["d_model"], p["d_ff"]))
            + (p["n_layers"] - dense) * (mla + moe_ffn_per_token(p))
            + 2 * p["d_model"] * p["vocab"])           # the head


def train_per_token(p: dict, seq: int) -> float:
    return 3.0 * forward_per_token(p, seq)


def experts_flops(p: dict, rows: float) -> float:
    """FLOPs of the grouped products over ``rows`` routed rows (summed
    over layers): 6·d·f forward and 12·d·f backward per row."""
    return 18.0 * p["d_model"] * p["moe_d_ff"] * rows


def experts_bytes(p: dict, rows: float, steps: int,
                  dtype_bytes: int = 2) -> float:
    """Bytes the grouped products must move over ``steps`` steps of
    ``rows`` routed rows in all: each pass (forward, backward through
    activations, backward through weights) reads every held expert's
    three weights once per MoE layer, and each row's input and output
    of width d."""
    d, f = p["d_model"], p["moe_d_ff"]
    layers = p["n_layers"] - p["first_dense_layers"]
    weights = steps * layers * held_experts(p) * 3 * d * f
    return 3.0 * dtype_bytes * (weights + rows * 2 * d)
