"""Device time per step of the Flare machinery (every ``flare.`` scope: the
FSDP gather and reduce-scatter, the reducer's pack, transports and
unpack), which on one chip has nothing to reduce: self time of the ops
made under those scopes (``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.ms_per(ctx, ("flare.",), "steps")
