"""Training cells: the program's own train step, one caller, closed loop.

Set-up builds the step that ``repro.train.trainer.jit_train_step`` makes
(the program ``repro.launch.train.run`` compiles), with its state, from
the seed, and takes the first ``checked_steps`` steps through the same
call and feed that the window then uses.  The window runs ``run``'s
loop: the next batch, the step, the loss read back.

What decides ``correct``: those first steps against the plain reference
(``bench/reference``), run once the window has closed and the program's
state is freed: each step's loss, each leaf's first gradient as AdamW
received it (read from its first moment, ``m₁ = (1 − b1)·g``), and each
leaf's change over the checked steps.
"""
from __future__ import annotations

import gc
import math
import time

import jax
import jax.numpy as jnp

from bench import compare, traffic, weights
from bench.reference import mamba2 as ref_mamba2
from bench.reference import train as ref_train

#: program families the reference covers
REFERENCE_LOSS = {"ssm": ref_mamba2.loss}


def model_config(config: dict):
    from repro.models.base import ModelConfig
    prog = dict(config["program"])
    prog["dtype"] = jnp.dtype(config["compute_dtype"])
    return ModelConfig(**prog)


def param_shapes(model):
    """The program's parameter tree as the configuration runs it.  With
    tied embeddings the tree holds no ``lm_head``: the program's head is
    then the embedding's transpose."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if model.cfg.tie_embeddings:
        shapes = {k: v for k, v in shapes.items() if k != "lm_head"}
    return shapes


def compiled_bytes(compiled) -> int:
    """The compiler's count of one chip's memory for a program: its
    arguments, outputs and temporaries, less what outputs alias."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def reference_config(config: dict) -> dict:
    p = config["program"]
    di = p.get("ssm_expand", 2) * p["d_model"]
    return {"norm_eps": p["norm_eps"], "ssm_headdim": p["ssm_headdim"],
            "ssm_heads": di // p["ssm_headdim"]}


def mesh_of(spec: str, chips: int):
    from jax.sharding import AxisType
    shape = tuple(int(v) for v in spec.split("x"))
    axes = ("data", "model") if len(shape) == 2 else ("pod", "data", "model")
    if math.prod(shape) != chips:
        raise ValueError(f"mesh {spec} needs {math.prod(shape)} chips, the "
                         f"cell has {chips}")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape),
                         devices=jax.devices()[:chips])


def delta_norms(a, b):
    return ref_train.leaf_norms(jax.tree.map(jnp.subtract, a, b))


class Driver:
    """One training cell, built from the seed."""

    def __init__(self, cell, seed: int):
        from repro.models import get_model
        from repro.core.engine import FlareConfig
        from repro.sharding import rules
        from repro.train import trainer

        self.cell = cell
        t = cell.traffic
        self.hp = t["optimizer"]
        self.cfg = model_config(cell.config)
        self.mesh = mesh_of(t["mesh"], cell.chips)
        mcfg = rules.MeshCfg(self.mesh.axis_names, tuple(self.mesh.shape.values()))
        self.world = mcfg.data_world
        self.batch = t["batch_per_chip"] * self.world
        self.seq = t["seq_len"]
        self.n_checked = t["checked_steps"]
        self.vocab = cell.config["vocab_size"]     # token ids drawn
        model = get_model(self.cfg)
        key = weights.key_from_seed(seed)
        self.wkey, self.bkey = jax.random.fold_in(key, 0), jax.random.fold_in(key, 1)

        self.shapes = param_shapes(model)
        sds = jax.ShapeDtypeStruct((self.batch, self.seq), jnp.int32)
        batch_shapes = {"tokens": sds, "labels": sds}
        tcfg = trainer.TrainConfig(
            lr=self.hp["lr"], weight_decay=self.hp["weight_decay"],
            clip_norm=self.hp["clip_norm"],
            flare=FlareConfig(axes=mcfg.reduce_axes, **t.get("flare", {})))
        with jax.set_mesh(self.mesh):
            fn, param_sh, opt_sh, batch_sh, init_opt = trainer.jit_train_step(
                model, self.mesh, mcfg, tcfg, self.shapes, batch_shapes,
                donate=True)
            self.init = jax.jit(lambda k: weights.init_params(self.shapes, k),
                                out_shardings=param_sh)
            params = self.init(self.wkey)
            opt = jax.jit(init_opt, out_shardings=opt_sh)(params)
            self.pool = jax.jit(
                lambda k: traffic.token_batches(
                    k, t["batch_pool"], vocab=self.vocab,
                    batch=self.batch, seq=self.seq, zipf_s=t["zipf_s"]),
                out_shardings=[batch_sh] * t["batch_pool"])(self.bkey)
            batch_in = jax.tree.map(
                lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                   sharding=sh),
                batch_shapes, batch_sh)
            self.step_fn = fn.lower(params, opt, batch_in).compile()
            self.compiled_bytes = compiled_bytes(self.step_fn)
            norms = jax.jit(ref_train.leaf_norms)
            deltas = jax.jit(delta_norms)

            # the first steps, through the window's own call and feed
            self.losses = []
            for i in range(self.n_checked):
                params, opt, metrics = self.step_fn(params, opt, self.pool[i])
                self.losses.append(float(metrics["loss"]))
                if i == 0:
                    self.g1 = [float(x) / (1.0 - self.hp["b1"])
                               for x in norms(opt["m"])]
            p0 = self.init(self.wkey)
            self.change = [float(x) for x in deltas(params, p0)]
            del p0
        self.params, self.opt = params, opt
        self.next = self.n_checked

    def window(self, seconds: float) -> dict:
        steps = failed = 0
        t0 = time.perf_counter()
        with jax.set_mesh(self.mesh):
            while True:
                with jax.profiler.TraceAnnotation("bench.next_batch"):
                    batch = self.pool[self.next % len(self.pool)]
                    self.next += 1
                with jax.profiler.TraceAnnotation("bench.step"):
                    self.params, self.opt, metrics = self.step_fn(
                        self.params, self.opt, batch)
                with jax.profiler.TraceAnnotation("bench.loss_read"):
                    loss = float(metrics["loss"])
                steps += 1
                failed += not math.isfinite(loss)
                if time.perf_counter() - t0 >= seconds:
                    break
        window_s = time.perf_counter() - t0
        return {"steps": steps, "tokens": steps * self.batch * self.seq,
                "window_s": window_s, "attempted": steps, "failed": failed}

    def end_to_end(self, counts: dict) -> dict:
        return {"tokens_per_s": counts["tokens"] / counts["window_s"]}

    def free(self):
        del self.params, self.opt, self.step_fn, self.pool
        gc.collect()

    # -- correctness -----------------------------------------------------
    def reference_batches(self, rows: int | None = None) -> list[tuple]:
        t = self.cell.traffic
        out = []
        for i in range(self.n_checked):
            b = jax.jit(lambda k, i=i: traffic.token_batch(
                k, i, vocab=self.vocab, batch=self.batch, seq=self.seq,
                zipf_s=t["zipf_s"]))(self.bkey)
            out.append((b["tokens"][:rows], b["labels"][:rows]))
        return out

    def reference(self, dot=ref_mamba2.exact_dot, rows: int | None = None):
        """(losses, first-gradient leaf norms, change leaf norms) of the
        plain reference, on one chip, from the same seed."""
        loss_of = REFERENCE_LOSS[self.cfg.family]
        rcfg = reference_config(self.cell.config)
        init = jax.jit(lambda k: weights.init_params(self.shapes, k))
        losses, g1, p3 = ref_train.run(
            lambda p, tk, lb: loss_of(rcfg, p, tk, lb, dot),
            init(self.wkey), self.reference_batches(rows), self.hp)
        change = [float(x) for x in jax.jit(delta_norms)(p3, init(self.wkey))]
        return losses, g1, change

    def program_readings(self):
        return self.losses, self.g1, self.change

    @staticmethod
    def gaps(prog, ref) -> dict:
        (lp, gp, cp), (lr, gr, cr) = prog, ref
        return {"loss_gap": max(abs(a - b) for a, b in zip(lp, lr, strict=True)),
                "grad_gap": compare.norm_gap(gp, gr),
                "change_gap": compare.norm_gap(cp, cr, keep=compare.moved(gr))}

    def check(self) -> dict:
        """The numbers compared (the program's state must be freed)."""
        return self.gaps(self.program_readings(), self.reference())
