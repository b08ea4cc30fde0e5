"""Device time per step of multi-head latent attention (``lm.mla``: the
query, latent and rope-key projections, the latent norm, YaRN, the
blocked online softmax and the output projection), forward, recompute
and backward of every layer: self time of the ops made under that scope
(``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.ms_per(ctx, ("lm.mla",), "steps")
