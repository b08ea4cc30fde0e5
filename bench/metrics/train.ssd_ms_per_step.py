"""Device time per step of the SSD (``mamba2.ssd``: the chunked scan with
its segment sums and cumulative sums, the dt and D terms), forward,
recompute and backward of every layer: self time of the ops made under
that scope (``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.ms_per(ctx, ("mamba2.ssd",), "steps")
