#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (not run by the
benchmark's own runs).

    python3 bench/calibrate.py --workload <name> --seeds 1 2 ... [--control-seeds 3]

For each seed: the program's numbers as a run compares them (set-up and
the checked steps, or a reduction call, with no window), against the
plain reference.  For the first ``--control-seeds`` seeds also:

- the control: the reference in the nearest precision below the one
  the configuration states, put in the program's place (training: every
  matrix product's operands rounded to float8_e4m3fn with one scale per
  tensor, for bfloat16 compute; reduction: the inputs and the sum
  rounded to bfloat16, for float32 gradients);
- the faults a cell can have, planted in the reference put in the
  program's place (training: half of the batch left out, the mean taken
  over the rest) or in the program (reduction: the exchange left out,
  half the ranks left out, one answer altered).

One JSON line per reading on standard output, and all of them in
``--out`` when given.
"""
import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def train_readings(drv, with_control: bool) -> list[dict]:
    import jax.numpy as jnp

    from bench.reference import train as ref_train

    prog = drv.program_readings()
    drv.free()
    ref = drv.reference()
    out = [{"kind": "program", **drv.gaps(prog, ref)}]
    if with_control:
        ctl = drv.reference(dot=ref_train.lowered_dot(jnp.float8_e4m3fn))
        out.append({"kind": "control_fp8", **drv.gaps(ctl, ref)})
        half = drv.reference(rows=drv.batch // 2)
        out.append({"kind": "fault_half_batch", **drv.gaps(half, ref)})
    return out


def reduce_readings(drv, with_control: bool) -> list[dict]:
    import jax
    import jax.numpy as jnp

    outs = drv.fn(*drv.xs)
    drv.free()
    out = [{"kind": "program", "reduce_rel_err": drv.errors(outs)}]
    if with_control:
        out.append({"kind": "control_bf16",
                    "reduce_rel_err": _control_err(drv)})
        w = drv.world
        faults = {
            "fault_no_exchange": list(drv.xs),
            "fault_half_ranks": [
                jnp.broadcast_to(jnp.sum(x[: w // 2], 0) * (w / (w // 2)),
                                 x.shape) for x in drv.xs],
            "fault_answer_altered": [o.at[0, 0].add(1.0) if i == 0 else o
                                     for i, o in enumerate(outs)],
        }
        with jax.set_mesh(drv.mesh):
            for name, f in faults.items():
                out.append({"kind": name, "reduce_rel_err": drv.errors(f)})
    return out


def _control_err(drv) -> float:
    """The reference in bfloat16 precision (inputs and sum), in the
    program's place, against the float32 reference."""
    import jax

    from bench.drivers.reduce import reference_sum, rel_errors

    with jax.set_mesh(drv.mesh):
        errs = jax.jit(lambda x: rel_errors(
            [s[None] for s in reference_sum(x, mantissa_bits=7)],
            reference_sum(x)))(drv.xs)
    return max(float(e) for e in errs)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from bench import spec
    cell = spec.cell(args.workload)

    import jax
    if jax.devices()[0].platform != "tpu":
        sys.exit("calibrate: no TPU")
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    lines = []
    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        drv = spec.driver(cell.driver).Driver(cell, seed)
        fn = train_readings if cell.driver == "train" else reduce_readings
        for r in fn(drv, i < args.control_seeds):
            r.update(workload=cell.workload, seed=seed,
                     seconds=time.perf_counter() - t0)
            lines.append(r)
            print(json.dumps(r), flush=True)
        del drv
    if args.out:
        with open(args.out, "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in lines)


if __name__ == "__main__":
    main()
