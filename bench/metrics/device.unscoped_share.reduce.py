"""Share of the device ops' self time, in a reduction cell, that falls
under none of the program's scopes (``bench/scopes.py``): what the
scoped metrics cannot attribute, mean over the chips."""
from bench import scopes


def read(ctx):
    return scopes.unscoped_of_run(ctx)
