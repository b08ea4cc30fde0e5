"""The whole call's share of the ICI roofline: bus bandwidth
(``bench/wire.py``: 2 (p − 1) / p of the unpadded gradient bytes per
call, whatever algorithm moves them) over the traced window, over one
chip's published ICI bandwidth.  Host clock around calls that each end
in ``block_until_ready``."""
from bench import wire


def read(ctx):
    c = ctx["counts"]
    if not c.get("calls") or c["world"] < 2:
        return None
    bus = wire.allreduce_bus_bytes(c["bytes"], c["world"]) * c["calls"]
    return 100.0 * bus / c["window_s"] / ctx["peaks"]["ici_bytes_per_s"]
