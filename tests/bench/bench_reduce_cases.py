"""Runs the tiny reduction cell on four CPU devices, sound or with one
fault planted in ``GradReducer``; prints one JSON result per case.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python tests/bench/bench_reduce_cases.py sound no_exchange ...
"""
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, ROOT, os.path.join(ROOT, "src")]

import jax                                   # noqa: E402
import jax.numpy as jnp                      # noqa: E402
from bench_tiny import tiny_cell             # noqa: E402

from bench import run as bench_run          # noqa: E402
from repro.core.engine import GradReducer    # noqa: E402

REAL = GradReducer.__call__


def no_exchange(self, grads, state=None):
    return list(grads), None


def half_ranks(self, grads, state=None):
    ax = self.config.axes[-1]
    i, w = jax.lax.axis_index(ax), jax.lax.axis_size(ax)
    return [jax.lax.psum(jnp.where(i < w // 2, g, 0.0), ax) * (w / (w // 2))
            for g in grads], None


def answer_altered(self, grads, state=None):
    out, st = REAL(self, grads, state)
    first = out[0].reshape(-1).at[0].add(1.0).reshape(out[0].shape)
    return [first] + list(out[1:]), st


FAULTS = {"no_exchange": no_exchange, "half_ranks": half_ranks,
          "answer_altered": answer_altered}


def main(cases):
    for case in cases:
        GradReducer.__call__ = FAULTS.get(case, REAL)
        res = bench_run.run_cell(tiny_cell("grads-dp4.mamba2-370m"), 2**35 + 1,
                                 0.2, False, time.perf_counter())
        print(json.dumps({"case": case, **res}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
