"""Device time per call of the ring reduce-scatter
(``flare.ring.reduce_scatter``: each round's chunk selects, combines and
the waits on its collective-permute): self time of the ops made under
that scope (``bench/scopes.py``), mean over the chips."""
from bench import scopes


def read(ctx):
    return scopes.ms_per(ctx, ("flare.ring.reduce_scatter",), "calls")
