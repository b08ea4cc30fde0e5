"""Device time per step of the optimizer (``train.clip``: the global norm
and the scaling; ``train.adamw``: the update): self time of the ops made
under those scopes (``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.ms_per(ctx, ("train.clip", "train.adamw"), "steps")
