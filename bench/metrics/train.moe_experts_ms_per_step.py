"""Device time per step of the held experts' grouped products
(``lm.moe.experts``: gate, up and down over the rows sorted by expert,
forward and backward): self time of the ops made under that scope
(``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.ms_per(ctx, ("lm.moe.experts",), "steps")
