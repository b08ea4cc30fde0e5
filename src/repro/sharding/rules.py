"""Single source of truth for how every tensor is partitioned.

Sharding strategy (DESIGN.md §5):
  * **TP/EP over ``model``** — attention projections on the flattened
    head dim, MLP ffn dims, expert (E) dim, vocab/positional tables.
  * **FSDP/ZeRO over ``data``** — every ≥64 Ki-element matrix is sharded
    on a non-TP dim; gathered per-layer inside the scan through
    ``core.fsdp.gather_params`` (whose backward IS the Flare gradient
    reduce-scatter).  Parameters are replicated across ``pod``; the
    gradient tree's pod level is handled by the two-level collective.
  * small tensors (norms, biases, gates) replicate; their gradients go
    through the ``GradReducer`` engine.

Three consumers, one ``decide`` function:
  1. ``param_specs``  → full ``PartitionSpec``s (device_put / jit) and
     manual specs (``shard_map`` in_specs, data axes only);
  2. ``make_gather``  → the per-layer FSDP gather closure models call;
  3. ``cache_specs``  → KV/SSM cache partitioning for serving.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import fsdp as fsdp_mod

#: leading-axis-stacked parameter collections (per-layer scan stacks)
STACKED_ROOTS = frozenset({
    "layers", "local_layers", "global_layers", "cross_layers",
    "enc_layers", "dec_layers",
})

MIN_FSDP_SIZE = 1 << 16


@dataclasses.dataclass(frozen=True)
class MeshCfg:
    """Logical mesh: ('pod',)? + 'data' + 'model'."""

    axes: tuple[str, ...]
    shape: tuple[int, ...]

    @property
    def tp(self) -> int:
        return self.shape[self.axes.index("model")]

    @property
    def fsdp(self) -> int:
        return self.shape[self.axes.index("data")]

    @property
    def reduce_axes(self) -> tuple[str, ...]:
        """Gradient-reduction axes, outer→inner: ('pod','data') or ('data',)."""
        return tuple(a for a in self.axes if a != "model")

    @property
    def world(self) -> int:
        return math.prod(self.shape)

    @property
    def data_world(self) -> int:
        return math.prod(s for a, s in zip(self.axes, self.shape)
                         if a != "model")


#: leaf name → (tp_dim, fsdp_dim) for 2D weights; 3D expert weights and
#: special cases handled in ``decide``.
_RULES_2D = {
    "wq": (1, 0), "wk": (1, 0), "wv": (1, 0), "wo": (0, 1),
    "w_gate": (1, 0), "w_up": (1, 0), "w_down": (0, 1),
    "w_dkv": (1, 0), "w_kr": (None, 0), "w_ukv": (1, 0),
    "wz": (1, 0), "wx": (1, 0), "wb": (None, 0), "wc": (None, 0),
    "wdt": (None, 0), "out_proj": (0, 1),
    "router": (None, 0),
    "embed": (0, 1), "lm_head": (1, 0),
    "dec_pos": (None, 0), "enc_pos": (None, 0),
    "conv_xw": (1, None), "conv_bw": (None, None), "conv_cw": (None, None),
}


def decide(name: str, shape: tuple[int, ...], *, tp: int, fsdp: int,
           local_shard: bool = False) -> tuple[int | None, int | None]:
    """(tp_dim, fsdp_dim) for one *sliced* (no stack axis) leaf.

    ``local_shard=True`` means ``shape`` is the per-rank FSDP shard (the
    gather closure sees these): the size threshold scales by ``fsdp`` and
    divisibility was already established on the global shape.
    """
    if len(shape) >= 3 and name in ("w_gate", "w_up", "w_down"):
        # expert-parallel MoE weights (E, D, F)/(E, F, D): EP over E
        tp_dim, fsdp_dim = 0, 1
    elif len(shape) < 2:
        return None, None
    elif name in _RULES_2D:
        tp_dim, fsdp_dim = _RULES_2D[name]
    else:
        tp_dim, fsdp_dim = None, (0 if len(shape) >= 2 else None)

    if tp_dim is not None and shape[tp_dim] % tp:
        tp_dim = None
    size = math.prod(shape) * (fsdp if local_shard else 1)
    if fsdp_dim is not None and (size < MIN_FSDP_SIZE
                                 or (not local_shard
                                     and shape[fsdp_dim] % fsdp)
                                 or fsdp_dim == tp_dim):
        fsdp_dim = None
    return tp_dim, fsdp_dim


def _leaf_name(path) -> tuple[str, bool]:
    """(leaf rule name, stacked?) from a tree path."""
    keys = [p.key for p in path if hasattr(p, "key")]
    stacked = bool(keys) and keys[0] in STACKED_ROOTS
    return keys[-1] if keys else "", stacked


@dataclasses.dataclass(frozen=True)
class SpecTriple:
    full: P
    manual: P
    fsdp_dim: int | None


def _specs_for(name: str, shape, stacked: bool, mesh: MeshCfg) -> SpecTriple:
    sliced = shape[1:] if stacked else shape
    tp_dim, fsdp_dim = decide(name, tuple(sliced), tp=mesh.tp,
                              fsdp=mesh.fsdp)
    full = [None] * len(shape)
    manual = [None] * len(shape)
    off = 1 if stacked else 0
    if tp_dim is not None:
        full[tp_dim + off] = "model"
    if fsdp_dim is not None:
        full[fsdp_dim + off] = "data"
        manual[fsdp_dim + off] = "data"
    return SpecTriple(P(*full), P(*manual), fsdp_dim)


def param_specs(params_tree: Any, mesh: MeshCfg):
    """(full_specs, manual_specs, fsdp_dims) pytrees for a params tree."""
    def f(path, leaf):
        name, stacked = _leaf_name(path)
        return _specs_for(name, leaf.shape, stacked, mesh)
    triples = jax.tree_util.tree_map_with_path(f, params_tree)
    is_leaf = lambda x: isinstance(x, SpecTriple)
    full = jax.tree.map(lambda t: t.full, triples, is_leaf=is_leaf)
    manual = jax.tree.map(lambda t: t.manual, triples, is_leaf=is_leaf)
    # -1 sentinel (not None: None leaves vanish from pytrees)
    dims = jax.tree.map(lambda t: -1 if t.fsdp_dim is None else t.fsdp_dim,
                        triples, is_leaf=is_leaf)
    return full, manual, dims


#: leaves that must stay fp32 through the compute path (SSM dynamics,
#: MoE router logits)
KEEP_F32 = frozenset({"A_log", "D", "dt_bias", "router"})


def cast_params(params_tree: Any, dtype) -> Any:
    """Cast float leaves to the compute dtype (KEEP_F32 names exempt)."""
    def f(path, leaf):
        name, _ = _leaf_name(path)
        if name in KEEP_F32 or not jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf
        if isinstance(leaf, jax.ShapeDtypeStruct):
            return jax.ShapeDtypeStruct(leaf.shape, dtype)
        return leaf.astype(dtype)
    return jax.tree_util.tree_map_with_path(f, params_tree)


def make_gather(mesh: MeshCfg, algorithm: str, params_tree: Any,
                compute_dtype=None):
    """FSDP gather closure passed to models (applied to sliced layer dicts).

    For each leaf of the (sliced) layer dict that the rules mark FSDP,
    all-gather it over the data axis via ``core.fsdp.gather_params`` —
    whose custom VJP reduce-scatters the gradient over ``data`` and
    all-reduces it over ``pod``: the paper's reduction tree, per layer.

    Decisions are precomputed from the *global* params tree and keyed by
    (leaf name, local shard shape): a local shape alone cannot
    distinguish "unsharded" from "shard of a 16× larger global".

    ``compute_dtype``: fp32 master shards are cast *before* the gather —
    bf16 on the wire both ways (gather fwd, reduce-scatter bwd), fp32
    only in the optimizer.  KEEP_F32 leaves are exempt.
    """
    axes = mesh.reduce_axes
    lookup: dict[tuple[str, tuple[int, ...]], int] = {}

    def record(path, leaf):
        name, stacked = _leaf_name(path)
        sliced = tuple(leaf.shape[1:] if stacked else leaf.shape)
        _, fsdp_dim = decide(name, sliced, tp=mesh.tp, fsdp=mesh.fsdp)
        local = list(sliced)
        if fsdp_dim is not None:
            local[fsdp_dim] //= mesh.fsdp
        key = (name, tuple(local))
        val = -1 if fsdp_dim is None else fsdp_dim
        if lookup.get(key, val) != val:
            raise ValueError(f"ambiguous FSDP decision for {key}")
        lookup[key] = val
        return leaf
    jax.tree_util.tree_map_with_path(record, params_tree)

    def gather(layer_tree):
        def f(path, leaf):
            name, _ = _leaf_name(path)
            if not hasattr(leaf, "shape"):
                return leaf
            if compute_dtype is not None and name not in KEEP_F32 \
                    and jnp.issubdtype(leaf.dtype, jnp.floating):
                leaf = leaf.astype(compute_dtype)
            fsdp_dim = lookup.get((name, tuple(leaf.shape)), -1)
            if fsdp_dim < 0:
                return leaf
            return fsdp_mod.gather_params(leaf, axes, algorithm, fsdp_dim)
        return jax.tree_util.tree_map_with_path(f, layer_tree)
    return gather


def shard_fsdp_leaves(params: Any, mesh: MeshCfg):
    """What the *sharded* params look like (shapes divided on FSDP dims).

    Used to build ShapeDtypeStructs for the dry-run without allocation.
    """
    def f(path, leaf):
        name, stacked = _leaf_name(path)
        sliced = leaf.shape[1:] if stacked else leaf.shape
        _, fsdp_dim = decide(name, tuple(sliced), tp=mesh.tp, fsdp=mesh.fsdp)
        if fsdp_dim is None:
            return leaf
        off = 1 if stacked else 0
        shape = list(leaf.shape)
        shape[fsdp_dim + off] //= mesh.fsdp
        return jax.ShapeDtypeStruct(tuple(shape), leaf.dtype)
    return jax.tree_util.tree_map_with_path(f, params)


# ---------------------------------------------------------------------------
# Batch and cache specs.
# ---------------------------------------------------------------------------

def batch_spec(batch_tree: Any, mesh: MeshCfg):
    """Shard the leading batch dim over (pod, data) when divisible."""
    daxes = tuple(a for a in mesh.axes if a != "model")
    dworld = mesh.data_world

    def f(leaf):
        if not leaf.shape:
            return P()
        if leaf.shape[0] % dworld == 0:
            return P(daxes)
        if leaf.shape[0] % mesh.fsdp == 0:
            return P(("data",))
        return P()
    return jax.tree.map(f, batch_tree)


_CACHE_SEQ_DIM = {"k": 2, "v": 2, "c_kv": 2, "k_rope": 2,
                  "xk": 2, "xv": 2}
_CACHE_HEAD_DIM = {"k": 3, "v": 3, "xk": 3, "xv": 3, "ssm": 2}
_CACHE_FEAT_DIM = {"conv_x": 3, "conv_b": 3, "conv_c": 3}


def cache_specs(cache_tree: Any, mesh: MeshCfg):
    """Partition KV/SSM caches: batch over data; heads (if divisible)
    else sequence over model — long-context decode shards the context."""
    daxes = tuple(a for a in mesh.axes if a != "model")
    dworld = mesh.data_world

    def f(path, leaf):
        keys = [p.key for p in path if hasattr(p, "key")]
        name = keys[-1] if keys else ""
        if not hasattr(leaf, "shape") or not leaf.shape:
            return P()
        spec = [None] * leaf.ndim
        # batch dim: stacked caches are (L, B, ...)
        if leaf.ndim >= 2:
            if leaf.shape[1] % dworld == 0:
                spec[1] = daxes
            elif leaf.shape[1] % mesh.fsdp == 0:
                spec[1] = "data"
        # model axis: heads if divisible, else sequence, else feature dim
        for dim_map in (_CACHE_HEAD_DIM, _CACHE_SEQ_DIM, _CACHE_FEAT_DIM):
            d = dim_map.get(name)
            if d is not None and d < leaf.ndim and spec[d] is None \
                    and leaf.shape[d] % mesh.tp == 0:
                spec[d] = "model"
                break
        return P(*spec)
    return jax.tree_util.tree_map_with_path(f, cache_tree)
