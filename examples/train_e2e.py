"""End-to-end training driver: a small llama-family model, a few hundred
steps, full Flare stack (FSDP gather/reduce-scatter + GradReducer +
AdamW + checkpointing) on 4 fake devices.

Run:  PYTHONPATH=src python examples/train_e2e.py [--steps 200]
Scale up with --d-model/--layers/--steps.  This example always runs on
the CPU; on a TPU use ``python -m repro.launch.train`` or
``chip_smoke.py``.
"""
import argparse
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
os.environ["JAX_PLATFORMS"] = "cpu"

import time

import jax
import jax.numpy as jnp

from repro.core.engine import FlareConfig
from repro.data import pipeline
from repro.ft import CheckpointManager
from repro.models import get_model
from repro.models.base import ModelConfig
from repro.sharding import rules
from repro.train import trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--vocab", type=int, default=2048)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--algorithm", type=str, default="auto")
    ap.add_argument("--ckpt", type=str, default="/tmp/flare_e2e_ckpt")
    args = ap.parse_args()

    cfg = ModelConfig(
        name="e2e", family="dense", n_layers=args.layers,
        d_model=args.d_model, n_heads=4, n_kv_heads=2,
        head_dim=args.d_model // 4, d_ff=4 * args.d_model,
        vocab=args.vocab, dtype=jnp.float32)
    model = get_model(cfg)

    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    mcfg = rules.MeshCfg(("data", "model"), (2, 2))
    tcfg = trainer.TrainConfig(
        lr=args.lr,
        flare=FlareConfig(axes=("data",), algorithm=args.algorithm))

    key = jax.random.PRNGKey(0)
    batch0 = next(pipeline.synthetic_batches(cfg, args.batch, args.seq,
                                             prefetch=False))
    batch_shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), batch0)

    with jax.set_mesh(mesh):
        fn, param_sh, opt_sh, batch_sh, init_opt = trainer.jit_train_step(
            model, mesh, mcfg, tcfg, jax.eval_shape(model.init, key),
            batch_shapes)
        params = jax.device_put(model.init(key), param_sh)
        opt = jax.device_put(init_opt(params), opt_sh)
        cm = CheckpointManager(args.ckpt, keep=2)

        n_params = sum(x.size for x in jax.tree.leaves(params))
        print(f"training {n_params/1e6:.1f}M params on 2x2 mesh, "
              f"{args.steps} steps")
        stream = pipeline.synthetic_batches(cfg, args.batch, args.seq,
                                            shardings=batch_sh, seed=1)
        t0 = time.time()
        for step in range(args.steps):
            params, opt, m = fn(params, opt, next(stream))
            if step % 20 == 0 or step == args.steps - 1:
                print(f"  step {step:4d} loss {float(m['loss']):7.4f} "
                      f"gnorm {float(m['grad_norm']):6.3f}")
            if (step + 1) % 100 == 0:
                cm.save(step + 1, {"params": params, "opt": opt})
        cm.wait()
        dt = time.time() - t0
        toks = args.steps * args.batch * args.seq
        print(f"done: {dt:.1f}s, {toks/dt:.0f} tok/s, "
              f"checkpoints at {args.ckpt}: steps {cm.all_steps()}")


if __name__ == "__main__":
    main()
