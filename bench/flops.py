"""Model FLOPs per token, from the configuration's sizes alone.

Counts the matrix products the model requires (2 FLOPs per
multiply-add), including the chunked SSD's in-chunk and state products,
and leaves out elementwise work and everything recomputed in backward.
Training is three forward passes' worth: forward, then backward through
activations and through weights.
"""
from __future__ import annotations


def mamba2_layer(p: dict) -> float:
    """One Mamba-2 mixer, forward, per token."""
    d, n = p["d_model"], p["ssm_state"]
    di = p.get("ssm_expand", 2) * d
    hd = p.get("ssm_headdim", 64)
    h = di // hd
    q = p.get("ssm_chunk", 256)
    k = p.get("ssm_conv", 4)
    proj = 2 * d * (2 * di + 2 * n + h) + 2 * di * d
    conv = 2 * k * (di + 2 * n)
    ssd = (2 * q * n            # C·Bᵀ within the chunk
           + 2 * h * q * hd     # (C·Bᵀ ∘ decay) · x
           + 2 * h * hd * n     # chunk-end states
           + 2 * h * hd * n)    # states → outputs
    return proj + conv + ssd


def forward_per_token(p: dict) -> float:
    """Forward FLOPs per token of an ``ssm`` model."""
    if p["family"] != "ssm":
        raise ValueError(f"no FLOP count for family {p['family']!r}")
    return p["n_layers"] * mamba2_layer(p) \
        + 2 * p["d_model"] * p["vocab"]               # the LM head


def train_per_token(p: dict) -> float:
    return 3.0 * forward_per_token(p)
