"""Model FLOPs utilisation of a MoE training step over the traced window:
model FLOPs per token (``bench/flops_moe.py``: MLA with causal scores,
the dense block, router, shared experts, the held experts at their
expected load, the head; recompute left out) times tokens trained, over
the window's seconds, the chips and their bf16 peak."""
from bench import flops_moe


def read(ctx):
    c = ctx["counts"]
    if not c.get("tokens"):
        return None
    per_token = flops_moe.train_per_token(ctx["cell"].config["program"],
                                          ctx["cell"].traffic["seq_len"])
    return 100.0 * per_token * c["tokens"] / (
        c["window_s"] * ctx["chips"] * ctx["peaks"]["bf16_flops"])
