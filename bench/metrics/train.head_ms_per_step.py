"""Device time per step of the head and loss (``lm.head_loss``: the final
norm, the tied head and the chunked cross-entropy), forward and
backward: self time of the ops made under that scope
(``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.ms_per(ctx, ("lm.head_loss",), "steps")
