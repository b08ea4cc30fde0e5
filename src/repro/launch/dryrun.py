import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The three lines above MUST stay the first statements of this module (jax
locks the device count at first init).  Run as::

    PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Per cell this script:
  1. builds the production mesh (16×16 single pod / 2×16×16 multi-pod),
  2. builds ShapeDtypeStruct stand-ins for params / optimizer / batch /
     cache (no allocation),
  3. ``jax.jit(step).lower(...).compile()`` — sharding bugs, compile-time
     OOM and unsupported collectives fail HERE,
  4. prints ``memory_analysis()`` + ``cost_analysis()`` and parses
     collective bytes from the partitioned HLO (§Roofline inputs),
  5. writes a JSON record under ``results/dryrun/``.
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.core.engine import FlareConfig
from repro.data import pipeline
from repro.launch import analytic, hlo_analysis, mesh as mesh_mod
from repro.models import get_model
from repro.sharding import rules
from repro.train import trainer


def input_specs(cfg, cell):
    """ShapeDtypeStruct stand-ins for every model input of a cell."""
    return pipeline.batch_structs(cfg, cell)


def _train_lowered(model, mesh, mcfg, cell, flare_algorithm="auto",
                   gather_algorithm="rhd"):
    cfg = model.cfg
    params_s = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch_s = input_specs(cfg, cell)
    tcfg = trainer.TrainConfig(
        gather_algorithm=gather_algorithm,
        flare=FlareConfig(axes=mcfg.reduce_axes, algorithm=flare_algorithm))
    fn, param_sh, opt_sh, batch_sh, _ = trainer.jit_train_step(
        model, mesh, mcfg, tcfg, params_s, batch_s, donate=True)
    opt_s = {"m": params_s, "v": params_s,
             "step": jax.ShapeDtypeStruct((), jnp.int32)}
    return fn.lower(params_s, opt_s, batch_s)


def _serve_lowered(model, mesh, mcfg, cell):
    cfg = model.cfg
    params_s = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    # inference weights live in the compute dtype (no fp32 master copies)
    params_s = rules.cast_params(params_s, cfg.dtype)
    full_specs, _, _ = rules.param_specs(params_s, mcfg)
    ns = lambda s: NamedSharding(mesh, s)
    param_sh = jax.tree.map(ns, full_specs)
    batch_s = input_specs(cfg, cell)
    bspec = rules.batch_spec(batch_s, mcfg)
    batch_sh = jax.tree.map(ns, bspec)

    if cell.kind == "prefill":
        cache_s = jax.eval_shape(
            lambda: model.init_cache(cell.global_batch, cell.seq_len))
        cache_sh = jax.tree.map(ns, rules.cache_specs(cache_s, mcfg))
        fn = jax.jit(model.prefill, in_shardings=(param_sh, batch_sh),
                     out_shardings=(None, cache_sh))
        return fn.lower(params_s, batch_s)

    # decode: one token against a seq_len cache
    cache_s = jax.eval_shape(
        lambda: model.init_cache(cell.global_batch, cell.seq_len))
    cache_sh = jax.tree.map(ns, rules.cache_specs(cache_s, mcfg))
    tok_s = batch_s["tokens"]
    tok_sh = batch_sh["tokens"]
    fn = jax.jit(model.decode, in_shardings=(param_sh, tok_sh, cache_sh),
                 out_shardings=(None, cache_sh), donate_argnums=(2,))
    return fn.lower(params_s, tok_s, cache_s)


def run_cell(arch: str, cell, *, multi_pod: bool, out_dir: str,
             save_hlo: bool = False, flare_algorithm: str = "auto",
             gather_algorithm: str = "rhd", tag: str = "",
             overrides: dict | None = None) -> dict:
    arch = configs.ALIASES.get(arch, arch)   # canonical module name
    mod = configs.load(arch)
    cfg = mod.CONFIG
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    model = get_model(cfg)
    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    mcfg = mesh_mod.mesh_cfg(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    chips = mcfg.world
    label = f"{arch}.{cell.name}.{mesh_name}" + (f".{tag}" if tag else "")
    t0 = time.time()

    with jax.set_mesh(mesh):
        if cell.kind == "train":
            lowered = _train_lowered(model, mesh, mcfg, cell,
                                     flare_algorithm, gather_algorithm)
        else:
            lowered = _serve_lowered(model, mesh, mcfg, cell)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_d = {k: getattr(mem, k) for k in
                 ("generated_code_size_in_bytes",
                  "argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes")
                 if hasattr(mem, k)}
    except Exception as e:                      # pragma: no cover
        mem, mem_d = None, {"error": str(e)}

    hlo = compiled.as_text()
    stats = hlo_analysis.analyze(hlo)           # trip-count corrected
    mf = analytic.model_flops(cfg, jax.eval_shape(model.init,
                                                  jax.random.PRNGKey(0)),
                              cell)
    # the partitioned HLO is the per-device program
    terms = hlo_analysis.roofline_terms(stats.flops, stats.bytes_accessed,
                                        stats.total_wire_bytes, chips)
    useful_ratio = (mf / chips) / stats.flops if stats.flops else 0.0

    record = {
        "arch": arch, "shape": cell.name, "kind": cell.kind,
        "mesh": mesh_name, "chips": chips,
        "seq_len": cell.seq_len, "global_batch": cell.global_batch,
        "flare_algorithm": flare_algorithm,
        "gather_algorithm": gather_algorithm,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "hlo_flops_per_device": stats.flops,
        "hlo_bytes_per_device": stats.bytes_accessed,
        "model_flops_global": mf,
        "useful_flops_ratio": useful_ratio,
        "raw_cost_analysis": {"flops": float(cost.get("flops", 0.0)),
                              "bytes": float(cost.get("bytes accessed",
                                                      0.0))},
        "memory_analysis": mem_d,
        "collectives": stats.as_dict(),
        "roofline": terms,
    }

    print(f"[dryrun] {label}")
    print(f"  lower {t_lower:.1f}s compile {t_compile:.1f}s")
    print(f"  memory_analysis: {mem_d}")
    print(f"  cost_analysis(raw): flops={cost.get('flops', 0):.3e}")
    print(f"  per-device: flops={stats.flops:.3e} "
          f"bytes={stats.bytes_accessed:.3e} "
          f"wire={stats.total_wire_bytes:.3e}")
    print(f"  model_flops(global)={mf:.3e} useful_ratio={useful_ratio:.3f}")
    print(f"  collectives: {stats.counts}")
    print(f"  roofline: compute={terms['compute_s']:.4f}s "
          f"memory={terms['memory_s']:.4f}s "
          f"collective={terms['collective_s']:.4f}s "
          f"dominant={terms['dominant']}")

    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, label + ".json"), "w") as f:
        json.dump(record, f, indent=1)
    if save_hlo:
        with open(os.path.join(out_dir, label + ".hlo"), "w") as f:
            f.write(hlo)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", type=str, default="results/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--flare-algorithm", type=str, default="auto")
    ap.add_argument("--gather-algorithm", type=str, default="rhd")
    ap.add_argument("--tag", type=str, default="")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (int or str), e.g. "
                         "--set attn_chunk=512 --set remat_policy=dots")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        overrides[k] = int(v) if v.lstrip("-").isdigit() else v

    cells = []
    if args.all:
        cells = configs.all_cells()
    else:
        mod = configs.load(args.arch)
        shapes = [s for s in mod.SHAPES
                  if args.shape in (None, s.name)]
        cells = [(args.arch, s) for s in shapes]

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = []
    for arch, cell in cells:
        for mp in meshes:
            mesh_name = "2x16x16" if mp else "16x16"
            label = f"{arch}.{cell.name}.{mesh_name}" \
                + (f".{args.tag}" if args.tag else "")
            path = os.path.join(args.out, label + ".json")
            if args.skip_existing and os.path.exists(path):
                print(f"[dryrun] skip {label} (exists)")
                continue
            try:
                run_cell(arch, cell, multi_pod=mp, out_dir=args.out,
                         save_hlo=args.save_hlo,
                         flare_algorithm=args.flare_algorithm,
                         gather_algorithm=args.gather_algorithm,
                         tag=args.tag, overrides=overrides)
            except Exception as e:
                traceback.print_exc()
                failures.append((label, repr(e)))
    if failures:
        print("\nFAILURES:")
        for l, e in failures:
            print(" ", l, e)
        raise SystemExit(1)
    print("\nall requested dry-run cells compiled OK")


if __name__ == "__main__":
    main()
