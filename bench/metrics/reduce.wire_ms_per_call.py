"""Device time per call in which a collective (collective-permute,
all-gather, reduce-scatter, all-reduce, all-to-all) was issued, in
flight or waited for: the union of their spans on the trace's op and
async-op lines, mean over the chips."""
from bench import trace


def read(ctx):
    tr, calls = ctx["trace"], ctx["counts"].get("calls")
    if tr is None or not calls:
        return None
    s = trace.collective_seconds(tr)
    return 1e3 * s / calls if s > 0 else None
