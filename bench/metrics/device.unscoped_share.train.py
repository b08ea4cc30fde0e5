"""Share of the device ops' self time, in a training cell, that falls
under none of the program's scopes (``bench/scopes.py``): what the
scoped metrics cannot attribute."""
from bench import scopes


def read(ctx):
    return scopes.unscoped_of_run(ctx)
