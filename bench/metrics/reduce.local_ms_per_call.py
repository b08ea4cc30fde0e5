"""Device time per call of every op that is not a collective: arena
pack and unpack, the combines, copies.  Self time (a loop's op less the
ops of its body) from the trace, mean over the chips."""
from bench import trace


def read(ctx):
    tr, calls = ctx["trace"], ctx["counts"].get("calls")
    if tr is None or not calls:
        return None
    s = trace.self_seconds(tr, collective=False)
    return 1e3 * s / calls if s > 0 else None
