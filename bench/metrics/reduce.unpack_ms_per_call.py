"""Device time per call of the arena unpack (``flare.unpack``: the reduced
buffers sliced back into the gradient leaves): self time of the ops
made under that scope (``bench/scopes.py``), mean over the chips."""
from bench import scopes


def read(ctx):
    return scopes.ms_per(ctx, ("flare.unpack",), "calls")
