"""FSDP parameter streaming routed through Flare collectives.

ZeRO/FSDP keeps each parameter sharded over the ``data`` (and ``pod``)
axes, all-gathers it just before use, and reduce-scatters its gradient.
That reduce-scatter *is* the leaf level of the paper's reduction tree —
so we make it first-class: ``gather_params`` is a ``custom_vjp`` whose
forward is a Flare all-gather and whose backward is a Flare
reduce-scatter (+ a fixed-tree allreduce over the pod axis in multi-pod
meshes — the root of the tree).  Selecting ``algorithm="fixed_tree"``
makes the FSDP gradient path bitwise-reproducible (F3).

Must be called inside a ``shard_map`` region where the reduction axes are
manual.  Sharding is along the leading array axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import collectives as coll


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def gather_params(shard: jax.Array, axes: tuple[str, ...],
                  algorithm: str = "ring", axis: int = 0) -> jax.Array:
    """All-gather a param sharded on ``axis``; bwd = Flare reduce-scatter.

    Forward: the FSDP shard (size/P_data along ``axis``) is all-gathered
    over the innermost reduction axis.  Backward: the full-parameter
    gradient is reduce-scattered over the innermost axis and fully reduced
    over the outer (pod) axes — the complete in-network gradient tree.
    """
    return _gather_impl(shard, axes, algorithm, axis)


def _alg(algorithm: str) -> str:
    """Map the engine-level algorithm names onto the gather/scatter pair."""
    return ("rhd" if algorithm in ("auto", "two_level", "hierarchical")
            else algorithm)


@jax.named_scope("flare.fsdp.gather")
def _gather_impl(shard, axes, algorithm, axis):
    *_, inner = axes
    x = jnp.moveaxis(shard, axis, 0) if axis else shard
    full = coll.all_gather(x, (inner,), algorithm=_alg(algorithm),
                           ordered=True)
    return jnp.moveaxis(full, 0, axis) if axis else full


def _gather_fwd(shard, axes, algorithm, axis):
    return _gather_impl(shard, axes, algorithm, axis), None


@jax.named_scope("flare.fsdp.reduce_scatter")
def _gather_bwd(axes, algorithm, axis, _res, g):
    x = jnp.moveaxis(g, axis, 0) if axis else g
    gs = coll.reduce_scatter(x, axes, algorithm=_alg(algorithm),
                             ordered=True)
    return (jnp.moveaxis(gs, 0, axis) if axis else gs,)


gather_params.defvjp(_gather_fwd, _gather_bwd)


def shard_leading(x: jax.Array, n: int) -> jax.Array:
    """Host-side helper: slice rank-local FSDP shard (used in tests)."""
    raise NotImplementedError("use jax.device_put with a NamedSharding; "
                             "this helper exists to fail loudly")


def fsdp_pad(x: jax.Array, p: int) -> jax.Array:
    """Pad leading axis to a multiple of the FSDP world size."""
    rem = (-x.shape[0]) % p
    if rem:
        pad = [(0, rem)] + [(0, 0)] * (x.ndim - 1)
        x = jnp.pad(x, pad)
    return x
