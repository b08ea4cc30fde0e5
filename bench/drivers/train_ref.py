"""Training cells whose plain reference the configuration names.

The same cell as ``bench/drivers/train.py`` (the program's own train
step, one caller, closed loop, and the same checks), for a model whose
reference is ``bench/reference/<reference>.py``, ``<reference>`` being
the configuration's ``reference`` key.  That module exposes ``loss(cfg,
params, tokens, labels, dot)`` over the configuration's ``program``
settings and its own ``exact_dot``; a new model adds its files only.

The window is ``bench/drivers/train.py``'s.  The step's counters (a
dropless MoE step's ``moe_rows``) stay on the device while it runs; once
it has closed they are read back, folded into a ``repro.obs`` metrics
registry and reported: ``moe_rows`` and ``moe_dropped`` over the window.
"""
from __future__ import annotations

import importlib

import jax

from bench import weights
from bench.drivers import train
from bench.reference import train as ref_train


def reference_module(config: dict):
    """``bench/reference/<config["reference"]>.py``."""
    return importlib.import_module(f"bench.reference.{config['reference']}")


class Driver(train.Driver):
    """One training cell with a named reference, built from the seed."""

    def __init__(self, cell, seed: int):
        from repro.models import get_model

        self.ref = reference_module(cell.config)
        super().__init__(cell, seed)
        # the step's counters (a program without any returns none)
        self.counters = getattr(get_model(self.cfg), "aux_names", ())

    def window(self, seconds: float) -> dict:
        kept, step = [], self.step_fn

        def counted(*args):
            out = step(*args)
            kept.append({n: out[2][n] for n in self.counters})
            return out
        self.step_fn = counted
        try:
            counts = super().window(seconds)
        finally:
            self.step_fn = step
        if "moe_rows" in self.counters:
            from repro.obs.metrics import MetricsRegistry, observe_moe
            registry = MetricsRegistry()
            for read in jax.device_get(kept):
                observe_moe(registry, read)
            counts["moe_rows"] = registry.value("moe.rows")
            counts["moe_dropped"] = registry.value("moe.dropped")
        return counts

    def reference(self, dot=None, rows: int | None = None, **fault):
        """(losses, first-gradient leaf norms, change leaf norms) of the
        configuration's reference, on one chip, from the same seed, over
        the first ``rows`` rows of each checked batch (all by default).
        ``fault`` overrides settings of the reference's configuration (a
        planted fault, e.g. ``capacity_factor``)."""
        rcfg = dict(self.cell.config["program"], **fault)
        dot = dot or self.ref.exact_dot
        init = jax.jit(lambda k: weights.init_params(self.shapes, k))
        losses, g1, p3 = ref_train.run(
            lambda p, tk, lb: self.ref.loss(rcfg, p, tk, lb, dot),
            init(self.wkey), self.reference_batches(rows), self.hp)
        change = [float(x)
                  for x in jax.jit(train.delta_norms)(p3, init(self.wkey))]
        return losses, g1, change
