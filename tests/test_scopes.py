"""The program's ``jax.named_scope``s reach the compiled HLO: the
reducer's phases, every transport and FSDP (four fake devices, in a
subprocess), and each layer of the Mamba-2 train step (one device)."""
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp

from multidevice_checks import _op_scopes

HERE = os.path.dirname(os.path.abspath(__file__))


def test_reducer_transports_and_fsdp_carry_their_scopes():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(HERE, "..", "src"),
                    os.environ.get("PYTHONPATH", "")]))
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "multidevice_checks.py"),
         "scopes"], capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    assert "scopes OK" in r.stdout


def test_mamba2_train_step_carries_its_layer_scopes():
    from repro.models import get_model
    from repro.models.base import ModelConfig
    from repro.sharding import rules
    from repro.train import trainer

    cfg = ModelConfig(name="tiny", family="ssm", n_layers=2, d_model=64,
                      n_heads=0, n_kv_heads=0, d_ff=0, vocab=256,
                      ssm_state=16, ssm_expand=2, ssm_headdim=16,
                      ssm_chunk=32, ssm_conv=4, tie_embeddings=True,
                      dtype=jnp.bfloat16)
    model = get_model(cfg)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    mcfg = rules.MeshCfg(("data", "model"), (1, 1))
    shapes = {k: v for k, v in jax.eval_shape(
        model.init, jax.random.PRNGKey(0)).items() if k != "lm_head"}
    sds = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    batch = {"tokens": sds, "labels": sds}
    with jax.set_mesh(mesh):
        fn, *_, init_opt = trainer.jit_train_step(
            model, mesh, mcfg, trainer.TrainConfig(), shapes, batch)
        opt = jax.eval_shape(init_opt, shapes)
        text = fn.lower(shapes, opt, batch).compile().as_text()
    found = _op_scopes(text)
    assert {"lm.embed", "mamba2.layer", "mamba2.in_proj", "mamba2.conv",
            "mamba2.ssd", "mamba2.out", "lm.head_loss", "train.clip",
            "train.adamw", "flare.pack"} <= found, found
    # the backward pass (and its recompute) keeps the layer's scopes
    # under JAX's wrappers
    assert re.search(r'op_name="[^"]*transpose\(jvp\(mamba2\.layer\)\)'
                     r'[^"]*/mamba2\.ssd/', text)
