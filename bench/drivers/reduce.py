"""Reduction cells: ``GradReducer`` on a whole gradient tree, back to back.

Every rank holds its own fp32 gradient of every leaf of the model (the
tree's shapes come from the program's ``eval_shape`` of its init; the
values from the seed), and, as the configuration's deployment does, its
replica of the fp32 weights and both AdamW moments, which the window
leaves untouched.  One call is ``GradReducer.__call__`` under a jitted
``jax.shard_map`` over every mesh axis, as a data-parallel step runs it
after backward, and ends in ``block_until_ready``.

What decides ``correct``: the outputs of two calls of the window — one
at a call index drawn from the seed, and the last — on every rank and
every leaf, against the plain sum of the ranks' inputs, run once the
window has closed.  The number compared is the worst leaf's largest
element error over the largest element of its reference sum.
"""
from __future__ import annotations

import gc
import math
import random
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import weights, wire
from bench.drivers.train import (compiled_bytes, mesh_of, model_config,
                                 param_shapes)


def reference_sum(xs, mantissa_bits: int | None = None):
    """The sum over ranks (leading axis) of each leaf.  With
    ``mantissa_bits`` (the control passes bfloat16's 7) the inputs and
    the sum are rounded to that precision by ``reduce_precision``, which
    the compiler may not drop as it may drop a pair of converts."""
    if mantissa_bits is None:
        return [jnp.sum(x, 0) for x in xs]
    rnd = lambda v: jax.lax.reduce_precision(v, 8, mantissa_bits)  # noqa: E731
    return [rnd(jnp.sum(rnd(x), 0)) for x in xs]


def rel_errors(outs, refs):
    """Per leaf: max |out − ref| over every rank, over max |ref|."""
    return [jnp.max(jnp.abs(o - r[None])) / jnp.max(jnp.abs(r))
            for o, r in zip(outs, refs, strict=True)]


class Driver:
    def __init__(self, cell, seed: int):
        from repro.core.engine import FlareConfig, GradReducer
        from repro.models import get_model

        self.cell = cell
        t = cell.traffic
        self.mesh = mesh_of(t["mesh"], cell.chips)
        axes = self.mesh.axis_names
        red_axes = tuple(a for a in axes if a != "model")
        self.world = math.prod(self.mesh.shape[a] for a in red_axes)
        shapes = param_shapes(get_model(model_config(cell.config)))
        self.leaves = jax.tree.leaves(shapes)
        self.elements = sum(math.prod(s.shape) for s in self.leaves)
        self.bytes = 4 * self.elements
        reducer = GradReducer(FlareConfig(axes=red_axes, **t.get("flare", {})))
        spec = P(red_axes)
        sh = NamedSharding(self.mesh, spec)
        n = len(self.leaves)

        def body(*xs):
            out, _ = reducer([x[0] for x in xs])
            return tuple(o[None] for o in out)

        key = weights.key_from_seed(seed)
        with jax.set_mesh(self.mesh):
            self.xs = jax.jit(
                lambda k: jax.tree.leaves(
                    weights.gradient_tree(shapes, k, self.world)),
                out_shardings=[sh] * n)(jax.random.fold_in(key, 0))
            # each rank's replica of the weights and the AdamW moments
            self.state = jax.jit(
                lambda k: [weights.init_params(shapes, k),
                           jax.tree.map(jnp.zeros_like, shapes),
                           jax.tree.map(jnp.zeros_like, shapes)],
                out_shardings=NamedSharding(self.mesh, P()))(
                    jax.random.fold_in(key, 1))
            fn = jax.jit(jax.shard_map(body, in_specs=(spec,) * n,
                                       out_specs=(spec,) * n,
                                       axis_names=set(axes), check_vma=False))
            self.fn = fn.lower(*self.xs).compile()
            self.compiled_bytes = compiled_bytes(self.fn)
            jax.block_until_ready(self.fn(*self.xs))
        # the call whose output is checked besides the last
        self.sample_at = random.Random(seed).randrange(t["sample_calls"])
        self.kept: dict[str, list] = {}

    def window(self, seconds: float) -> dict:
        calls = 0
        out = None
        t0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.call"):
                out = self.fn(*self.xs)
            with jax.profiler.TraceAnnotation("bench.block"):
                jax.block_until_ready(out)
            if calls == self.sample_at:
                self.kept["sampled"] = out
            calls += 1
            if time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
        self.kept["last"] = out
        return {"calls": calls, "window_s": window_s,
                "bytes": self.bytes, "world": self.world,
                "attempted": calls, "failed": 0}

    def end_to_end(self, counts: dict) -> dict:
        bus = wire.allreduce_bus_bytes(self.bytes, self.world)
        return {"reduce_busbw_GBps":
                bus * counts["calls"] / counts["window_s"] / 1e9}

    def free(self):
        del self.fn, self.state
        gc.collect()

    def errors(self, outs) -> float:
        with jax.set_mesh(self.mesh):
            errs = jax.jit(lambda o, x: rel_errors(o, reference_sum(x)))(
                list(outs), self.xs)
        return max(float(e) for e in errs)

    def check(self) -> dict:
        err = max(self.errors(o) for o in self.kept.values())
        self.kept.clear()
        return {"reduce_rel_err": err}

