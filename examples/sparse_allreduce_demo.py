"""The paper's experiment in miniature: dense vs sparse vs int8 gradient
reduction, wire bytes and convergence, on one model.

Run:  PYTHONPATH=src python examples/sparse_allreduce_demo.py
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core.engine import FlareConfig
from repro.core.sparse import expected_sparse_wire_bytes
from repro.core import collectives as coll
from repro.models import get_model
from repro.sharding import rules
from repro.train import trainer

cfg = configs.load("tinyllama-1.1b").SMOKE.scaled(dtype=jnp.float32)
model = get_model(cfg)
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
mcfg = rules.MeshCfg(("data", "model"), (4, 2))
key = jax.random.PRNGKey(0)
batch = {"tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab),
         "labels": jax.random.randint(key, (8, 32), 0, cfg.vocab)}
batch_shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                            batch)

MODES = {
    "dense_ring": FlareConfig(axes=("data",), algorithm="ring"),
    "reproducible": FlareConfig(axes=("data",), algorithm="fixed_tree",
                                reproducible=True),
    "int8": FlareConfig(axes=("data",), compression="int8"),
    "sparse_1pct": FlareConfig(axes=("data",), sparse_k_frac=0.01),
}

print(f"{'mode':<14}{'final loss':>12}{'grad wire bytes/rank':>24}")
for name, fc in MODES.items():
    tcfg = trainer.TrainConfig(lr=5e-3, flare=fc)
    with jax.set_mesh(mesh):
        fn, param_sh, opt_sh, batch_sh, init_opt = trainer.jit_train_step(
            model, mesh, mcfg, tcfg, jax.eval_shape(model.init, key),
            batch_shapes, donate=False)
        params = jax.device_put(model.init(key), param_sh)
        opt = jax.device_put(init_opt(params), opt_sh)
        bd = {k: jax.device_put(v, batch_sh[k]) for k, v in batch.items()}
        for _ in range(8):
            params, opt, m = fn(params, opt, bd)
    # wire accounting for a 1 MiB gradient bucket
    z = 1 << 20
    if fc.sparse_k_frac > 0:
        wire = expected_sparse_wire_bytes(z // 4, int(z // 4 * 0.01), 4)
    elif fc.compression == "int8":
        wire = 2 * z // 4
    else:
        wire = coll.wire_bytes_per_rank(
            z, 4, algorithm="ring" if name == "dense_ring" else "fixed_tree")
    print(f"{name:<14}{float(m['loss']):>12.4f}{wire:>20,.0f}")
print("\n(all modes converge; compressed/sparse modes move 4-50x fewer "
      "gradient bytes — the paper's F1/F2 trade)")
