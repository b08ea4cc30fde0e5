"""Model FLOPs utilisation of the training step over the traced window:
model FLOPs per token (``bench/flops.py``, recompute left out) times
tokens trained, over the window's seconds, the chips and their bf16
peak."""
from bench import flops


def read(ctx):
    c = ctx["counts"]
    if not c.get("tokens"):
        return None
    per_token = flops.train_per_token(ctx["cell"].config["program"])
    return 100.0 * per_token * c["tokens"] / (
        c["window_s"] * ctx["chips"] * ctx["peaks"]["bf16_flops"])
