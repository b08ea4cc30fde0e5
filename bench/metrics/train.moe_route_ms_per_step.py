"""Device time per step of the MoE layers' routing and data movement
(``lm.moe.route``: router, top-k, the sort by expert and the group
sizes; ``lm.moe.dispatch`` and ``lm.moe.combine``: the permutation of
rows into expert order and back, gate-weighted): self time of the ops
made under those scopes (``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.ms_per(
        ctx, ("lm.moe.route", "lm.moe.dispatch", "lm.moe.combine"), "steps")
