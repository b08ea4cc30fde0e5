"""The Flare train step: shard_map(manual: pod/data, auto: model).

Gradient flow — the paper's technique, end to end:
  * FSDP-sharded weights reach the model through
    ``core.fsdp.gather_params`` whose backward is a Flare ring/rhd/
    fixed-tree **reduce-scatter over data + allreduce over pod** — the
    in-network reduction tree, executed per layer as the backward scan
    walks the stack (compute/communication overlap falls out of the scan
    schedule: layer L's reduce-scatter overlaps layer L−1's backward).
  * Replicated leaves (norms, biases, routers) are reduced by the
    ``GradReducer`` engine on its flat-arena pipelined path: one padded
    buffer per dtype, all reduction blocks in one scanned/fused-wave
    computation (§6.2 multi-buffer), size-based algorithm switchover
    (§6.4), staggered block phases (§5), optional int8/top-k compression
    (F1/§7) with error feedback, optional bitwise-reproducible mode (F3).
  * The optimizer runs ZeRO-style on the local shards.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.engine import FlareConfig, GradReducer
from repro.sharding import rules
from repro.train import optim


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    lr: float = 3e-4
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    gather_algorithm: str = "rhd"     # FSDP collective (fixed_tree → F3)
    flare: FlareConfig = dataclasses.field(
        default_factory=lambda: FlareConfig())


def _split_by_fsdp(tree: Any, dims: Any):
    """Partition leaves into (fsdp, replicated) index sets."""
    leaves, treedef = jax.tree.flatten(tree)
    dim_leaves = jax.tree.leaves(dims)
    assert len(leaves) == len(dim_leaves), "params/dims tree mismatch"
    fsdp_idx = [i for i, d in enumerate(dim_leaves) if d >= 0]
    rep_idx = [i for i, d in enumerate(dim_leaves) if d < 0]
    return leaves, treedef, fsdp_idx, rep_idx


def make_train_step(model, mesh_cfg: rules.MeshCfg, tcfg: TrainConfig,
                    params_tree: Any, *, reduce_manager=None,
                    tenant: str | None = None):
    """Build the (un-jitted) SPMD train-step body + its shard_map wrapper.

    ``params_tree`` may be arrays or ShapeDtypeStructs — only the tree
    structure and shapes are read (to derive the sharding rules).
    ``reduce_manager``/``tenant`` attach this job's GradReducer to a
    shared multi-tenant switch runtime (``runtime.SessionManager``,
    ``transport="innetwork"``) so several training jobs in one process
    aggregate concurrently on one emulated switch.
    """
    full_specs, manual_specs, dims = rules.param_specs(params_tree, mesh_cfg)
    gather = rules.make_gather(mesh_cfg, tcfg.gather_algorithm, params_tree,
                               compute_dtype=model.cfg.dtype)
    reducer = GradReducer(tcfg.flare, manager=reduce_manager, tenant=tenant)
    reduce_axes = mesh_cfg.reduce_axes
    data_world = mesh_cfg.data_world

    def step_body(params, opt_state, batch):
        def loss_fn(p):
            # local-mean / data_world → summed gradients = global mean
            return model.loss(p, batch, gather=gather) / data_world

        loss, grads = jax.value_and_grad(loss_fn)(params)

        # --- replicated-leaf reduction through the Flare engine ----------
        g_leaves, treedef, fsdp_idx, rep_idx = _split_by_fsdp(grads, dims)
        if rep_idx:
            rep = [g_leaves[i] for i in rep_idx]
            red, ef = reducer(rep, opt_state.get("ef"))
            for i, r in zip(rep_idx, red):
                g_leaves[i] = r
        else:
            ef = None
        grads = jax.tree.unflatten(treedef, g_leaves)

        # --- global grad-norm clipping -----------------------------------
        with jax.named_scope("train.clip"):
            fsdp_ss = sum(jnp.sum(g_leaves[i].astype(jnp.float32) ** 2)
                          for i in fsdp_idx) if fsdp_idx else jnp.float32(0)
            rep_ss = sum(jnp.sum(g_leaves[i].astype(jnp.float32) ** 2)
                         for i in rep_idx) if rep_idx else jnp.float32(0)
            for ax in reduce_axes:
                fsdp_ss = (jax.lax.psum(fsdp_ss, ax) if ax == "data"
                           else fsdp_ss)
            gnorm = jnp.sqrt(fsdp_ss + rep_ss)
            scale = jnp.minimum(1.0, tcfg.clip_norm / (gnorm + 1e-9))
            grads = jax.tree.map(lambda g: g * scale, grads)

        # --- ZeRO update on local shards ----------------------------------
        with jax.named_scope("train.adamw"):
            new_params, new_opt = optim.adamw_update(
                params, grads, opt_state, lr=tcfg.lr,
                weight_decay=tcfg.weight_decay)
        if ef is not None:
            new_opt["ef"] = ef
        loss = jax.lax.psum(loss, reduce_axes)   # undo /data_world: global mean
        return new_params, new_opt, {"loss": loss, "grad_norm": gnorm}

    # --- shard_map wrapper -----------------------------------------------
    def wrap(batch_tree):
        bspec = rules.batch_spec(batch_tree, mesh_cfg)
        in_specs = ((manual_specs,
                     _opt_specs(manual_specs), bspec))
        out_specs = (manual_specs, _opt_specs(manual_specs),
                     {"loss": P(), "grad_norm": P()})
        # a size-1 axis partitions nothing, so it is manual too: a
        # compiled Pallas kernel (the in-network switch handlers) cannot
        # sit in a region that leaves any mesh axis auto
        manual = set(reduce_axes) | {
            a for a, n in zip(mesh_cfg.axes, mesh_cfg.shape) if n == 1}
        return jax.shard_map(
            step_body, in_specs=in_specs, out_specs=out_specs,
            axis_names=manual, check_vma=False)

    def _opt_specs(mspecs):
        d = {"m": mspecs, "v": mspecs, "step": P()}
        if reducer.needs_state:
            # EF state: list of replicated flat leaves
            _, _, _, rep_idx = _split_by_fsdp(params_tree, dims)
            leaves = jax.tree.leaves(params_tree)
            d["ef"] = [P() for _ in rep_idx]
        return d

    def init_opt_state(params):
        st = optim.adamw_init(params)
        if reducer.needs_state:
            leaves, _, _, rep_idx = _split_by_fsdp(params, dims)
            st["ef"] = reducer.init_state([leaves[i] for i in rep_idx])
        return st

    return step_body, wrap, full_specs, manual_specs, init_opt_state


def jit_train_step(model, mesh, mesh_cfg: rules.MeshCfg, tcfg: TrainConfig,
                   params_tree: Any, batch_tree: Any, donate: bool = True,
                   *, reduce_manager=None, tenant: str | None = None):
    """Fully-jitted train step with NamedShardings attached (for running
    and for the dry-run lower/compile)."""
    step_body, wrap, full_specs, manual_specs, init_opt = make_train_step(
        model, mesh_cfg, tcfg, params_tree, reduce_manager=reduce_manager,
        tenant=tenant)
    smapped = wrap(batch_tree)

    ns = lambda spec: NamedSharding(mesh, spec)
    param_sh = jax.tree.map(ns, full_specs)
    opt_sh = {"m": param_sh, "v": param_sh,
              "step": ns(P())}
    # EF state (if any) replicated
    reducer = GradReducer(tcfg.flare)
    if reducer.needs_state:
        _, _, dims = rules.param_specs(params_tree, mesh_cfg)
        _, _, _, rep_idx = _split_by_fsdp(params_tree, dims)
        opt_sh["ef"] = [ns(P()) for _ in rep_idx]
    bspec = rules.batch_spec(batch_tree, mesh_cfg)
    batch_sh = jax.tree.map(ns, bspec)
    out_sh = (param_sh, opt_sh, {"loss": ns(P()), "grad_norm": ns(P())})

    fn = jax.jit(smapped,
                 in_shardings=(param_sh, opt_sh, batch_sh),
                 out_shardings=out_sh,
                 donate_argnums=(0, 1) if donate else ())
    return fn, param_sh, opt_sh, batch_sh, init_opt
