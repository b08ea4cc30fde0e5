"""Device time per call of the arena pack (``flare.pack``: every dtype
group's leaves copied into its padded bucket buffer): self time of the
ops made under that scope, from the trace joined with the compiled
program (``bench/scopes.py``), mean over the chips."""
from bench import scopes


def read(ctx):
    return scopes.ms_per(ctx, ("flare.pack",), "calls")
