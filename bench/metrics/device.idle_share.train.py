"""Share of the traced window in which no op ran on the device (mean
over the chips), in a training cell."""
from bench import trace


def read(ctx):
    return trace.idle_percent(ctx["trace"])
