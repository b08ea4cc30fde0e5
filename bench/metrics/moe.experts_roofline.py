"""The held experts' grouped products against their v5e roofline: the
FLOPs and bytes the routed rows need (``bench/flops_moe.py``, from the
program's ``moe_rows`` counter over the window) over the device time
under ``lm.moe.experts``, as a share of the least time the chip could
take, ``max(FLOPs / bf16 peak, bytes / HBM bandwidth)``.  Recompute is
left out of the FLOPs, so the share reads below the work the device
did."""
from bench import flops_moe, scopes


def read(ctx):
    c = ctx["counts"]
    ms = scopes.ms_per(ctx, ("lm.moe.experts",), "steps")
    if not ms or not c.get("moe_rows"):
        return None
    p = ctx["cell"].config["program"]
    flops = flops_moe.experts_flops(p, c["moe_rows"])
    nbytes = flops_moe.experts_bytes(p, c["moe_rows"], c["steps"])
    least = max(flops / ctx["peaks"]["bf16_flops"],
                nbytes / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (ms * 1e-3 * c["steps"])
