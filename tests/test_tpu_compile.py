"""The switch-handler kernels compile for a TPU v5e chip at arena widths.

No chip is needed: the TPU compiler builds for a described ``v5e:2x2``
topology (four chips, none attached) and refuses what the chip would
refuse — blocks off the (8, 128) tiling, too much VMEM, a Mosaic kernel
left to XLA's partitioner.  ``ops._on_tpu`` is steered here so the
wrappers take their TPU branch (compiled kernel, no ``ref`` fallback)
while the process runs on the CPU.

The topology is described in a module fixture, never at import: only
one process at a time may load the TPU library.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops

P = 4          # children per switch level
MTU = 1024     # bytes per packet payload


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's compiles cannot be read back from the
    # persistent cache, so keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def _compiled_text(fn, *structs) -> str:
    # a fresh jit around the unjitted wrapper body: no trace cached by a
    # CPU caller of the same wrapper can stand in for the TPU branch
    return jax.jit(lambda *a: fn(*a)).lower(*structs).compile().as_text()


def _elems(dtype) -> int:
    return MTU // jnp.dtype(dtype).itemsize


@pytest.mark.parametrize("dtype,slots", [(jnp.float32, 1024),
                                         (jnp.bfloat16, 1027)],
                         ids=["f32-S1024", "bf16-S1027"])
def test_tree_reduce_slots_compiles(one_chip, on_tpu, dtype, slots):
    x = jax.ShapeDtypeStruct((P, slots, _elems(dtype)), dtype,
                             sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(
        ops.tree_reduce_slots.__wrapped__, x)


def test_tree_reduce_compiles(one_chip, on_tpu):
    x = jax.ShapeDtypeStruct((P, 1027 * _elems(jnp.float32)), jnp.float32,
                             sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(ops.tree_reduce.__wrapped__, x)


def test_dequant_accum_slots_compiles(one_chip, on_tpu):
    s, e, qblock = 1029, _elems(jnp.int8), 256
    q = jax.ShapeDtypeStruct((P, s, e), jnp.int8, sharding=one_chip)
    scales = jax.ShapeDtypeStruct((P, s, e // qblock), jnp.float32,
                                  sharding=one_chip)
    fn = functools.partial(ops.dequant_accum_slots.__wrapped__, qblock=qblock)
    assert "tpu_custom_call" in _compiled_text(fn, q, scales)


def test_sparse_accum_slots_compiles_many_buckets(one_chip, on_tpu):
    b, n, size = 12, 1000, 65536          # B > 1, B and n off the tiling
    idx = jax.ShapeDtypeStruct((b, n), jnp.int32, sharding=one_chip)
    val = jax.ShapeDtypeStruct((b, n), jnp.float32, sharding=one_chip)
    fn = functools.partial(ops.sparse_accum_slots.__wrapped__, size=size)
    assert "tpu_custom_call" in _compiled_text(fn, idx, val)


def test_innetwork_train_step_compiles_on_four_chips(topo, on_tpu):
    """The reproducible in-network step on a ``2x2x1`` mesh: its fixed-
    tree fold is a compiled kernel inside the step's ``shard_map``, which
    XLA refuses unless every mesh axis there is manual."""
    from jax.sharding import AxisType, Mesh, NamedSharding

    from repro import configs
    from repro.core.engine import FlareConfig
    from repro.models import get_model
    from repro.sharding import rules
    from repro.train import trainer

    cfg = configs.load("tinyllama-1.1b").SMOKE
    model = get_model(cfg)
    axes, shape = ("pod", "data", "model"), (2, 2, 1)
    mesh = Mesh(np.array(topo.devices).reshape(shape), axes,
                axis_types=(AxisType.Auto,) * 3)
    mcfg = rules.MeshCfg(axes, shape)
    tcfg = trainer.TrainConfig(gather_algorithm="fixed_tree", flare=FlareConfig(
        axes=mcfg.reduce_axes, reproducible=True, transport="innetwork"))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct((8, 64), jnp.int32)
             for k in ("tokens", "labels")}
    with jax.set_mesh(mesh):
        fn, param_sh, opt_sh, batch_sh, init_opt = trainer.jit_train_step(
            model, mesh, mcfg, tcfg, params, batch)
        opt = jax.eval_shape(init_opt, params)

        def placed(tree, sh):
            return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=s), tree, sh)

        text = fn.lower(placed(params, param_sh), placed(opt, opt_sh),
                        placed(batch, batch_sh)).compile().as_text()
    assert "tpu_custom_call" in text


def test_dropless_moe_layer_compiles_with_grouped_products(one_chip, on_tpu):
    """DeepSeek-V2-Lite's MoE layer at its published widths over 8 held
    experts, forward and backward: the grouped products are megablox
    kernels, and the chip's compiler takes them inside the token-chunk
    scan."""
    from repro import configs
    from repro.models import base, get_model

    cfg = configs.load("deepseek-v2-lite-16b").CONFIG.scaled(
        n_layers=2, experts_held=8, moe_chunk=512)
    shapes = jax.eval_shape(get_model(cfg).init, jax.random.PRNGKey(0))
    ffn = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype, sharding=one_chip),
        shapes["layers"]["moe"]["ffn"])
    x = jax.ShapeDtypeStruct((1, 1024, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)

    def grads(p, x):
        return jax.grad(lambda p: jnp.sum(
            base.moe_dropless(cfg, p, x)[0].astype(jnp.float32)))(p)
    text = _compiled_text(grads, ffn, x)
    assert text.count("tpu_custom_call") >= 3
