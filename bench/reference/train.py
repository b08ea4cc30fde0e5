"""Plain float32 training steps: loss, gradient, global-norm clip, AdamW.

Written from the configuration's stated optimizer (Loshchilov & Hutter,
arXiv:1711.05101, decoupled weight decay on every leaf, bias-corrected
moments) and clipping of the whole gradient to ``clip_norm``.  Imports
nothing from the program.

``run`` takes the first ``len(batches)`` steps and returns what the
harness compares: each step's loss, every leaf's norm of the first
gradient as the optimizer receives it (after clipping), and the final
parameters.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp


def leaf_norms(tree) -> list:
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


def scaled_round(fmt) -> Callable:
    """Round an operand to ``fmt`` with one scale per tensor (amax maps
    to the format's largest finite value), as a low-precision matmul
    path would, and return it in float32.  The scale is a constant to
    the gradient, and the rounding passes it straight through."""
    top = float(jnp.finfo(fmt).max)

    def rnd(x):
        amax = jnp.max(jnp.abs(x))
        scale = jax.lax.stop_gradient(jnp.where(amax > 0, amax / top, 1.0))
        return (x / scale).astype(fmt).astype(jnp.float32) * scale
    return rnd


def lowered_dot(fmt) -> Callable:
    """A ``dot`` whose operands are first rounded to ``fmt``."""
    rnd = scaled_round(fmt)

    def dot(spec, *xs):
        return jnp.einsum(spec, *(rnd(x) for x in xs),
                          precision=jax.lax.Precision.HIGHEST)
    return dot


def make_step(loss_fn: Callable, hp: dict):
    """One jitted AdamW step.  ``loss_fn(params, tokens, labels)`` is a
    mean over tokens; the batch is taken one row at a time and the rows'
    gradients averaged, which is the same mean."""
    b1, b2, eps = hp["b1"], hp["b2"], hp["eps"]
    lr, wd, clip = hp["lr"], hp["weight_decay"], hp["clip_norm"]
    vg = jax.value_and_grad(loss_fn)

    def loss_grad(params, tokens, labels):
        rows = tokens.shape[0]

        def body(acc, xs):
            l, g = vg(params, *(x[None] for x in xs))
            return jax.tree.map(jnp.add, acc, (l, g)), None
        zero = (jnp.float32(0), jax.tree.map(jnp.zeros_like, params))
        (l, g), _ = jax.lax.scan(body, zero, (tokens, labels))
        return l / rows, jax.tree.map(lambda x: x / rows, g)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, m, v, t, tokens, labels):
        loss, g = loss_grad(params, tokens, labels)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                             for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, clip / (gnorm + 1e-9)),
                         g)
        m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
        v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
        c1 = 1.0 - b1 ** t
        c2 = 1.0 - b2 ** t
        params = jax.tree.map(
            lambda p, a, s: p - lr * ((a / c1) / (jnp.sqrt(s / c2) + eps)
                                      + wd * p), params, m, v)
        return params, m, v, loss, leaf_norms(g)
    return step


def run(loss_fn: Callable, params, batches, hp: dict):
    """The first steps from ``params``: (losses, first-gradient leaf
    norms, final params).  ``batches`` is a list of (tokens, labels)."""
    step = make_step(loss_fn, hp)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, g1 = [], None
    for t, (tokens, labels) in enumerate(batches, 1):
        params, m, v, loss, gn = step(params, m, v, jnp.float32(t), tokens,
                                      labels)
        losses.append(float(loss))
        if g1 is None:
            g1 = [float(x) for x in gn]
    return losses, g1, params
