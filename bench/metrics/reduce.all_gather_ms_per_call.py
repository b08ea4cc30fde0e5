"""Device time per call of the ring all-gather (``flare.ring.all_gather``:
each round's writes of the received chunk and the waits on its
collective-permute): self time of the ops made under that scope
(``bench/scopes.py``), mean over the chips."""
from bench import scopes


def read(ctx):
    return scopes.ms_per(ctx, ("flare.ring.all_gather",), "calls")
