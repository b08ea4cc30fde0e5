#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for the training
cells, with the faults that ``bench/calibrate.py`` does not plant (not
run by the benchmark's own runs).

    python3 bench/calibrate_train.py --workload <name> --seeds 1 2 ... [--control-seeds 3]

For each seed: the program's numbers as a run compares them (set-up and
the checked steps, with no window), against the plain reference.  For
the first ``--control-seeds`` seeds also, each in the program's place:

- the control: the reference with every matrix product's operands
  rounded to float8_e4m3fn with one scale per tensor (the nearest
  precision below the configuration's bfloat16 compute);
- half of the batch left out, the mean taken over the rest;
- on more than one chip, the gradient exchange left out: the reference
  over the first chip's rows alone, which are all that chip trains on
  when no gradient crosses between the chips;
- for a mixture of experts, the capacity path: the reference with each
  expert keeping only its first 1.25 · S·k/E choices of a row, so that
  tokens over that are dropped.

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

#: the capacity factor of the planted capacity fault
CAPACITY = 1.25


def readings(drv, with_control: bool) -> list[dict]:
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
        if drv.world > 1:
            own = drv.reference(rows=drv.batch // drv.world)
            out.append({"kind": "fault_no_exchange", **drv.gaps(own, ref)})
        if drv.cell.config["program"].get("n_experts"):
            cap = drv.reference(capacity_factor=CAPACITY)
            out.append({"kind": "fault_capacity_1.25", **drv.gaps(cap, ref)})
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from bench import spec
    cell = spec.cell(args.workload)
    if cell.driver not in ("train", "train_ref"):
        sys.exit(f"calibrate_train: {cell.workload} is driven by "
                 f"{cell.driver!r}; use bench/calibrate.py")

    import jax
    if jax.devices()[0].platform != "tpu":
        sys.exit("calibrate_train: no TPU")
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    lines = []
    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        drv = spec.driver(cell.driver).Driver(cell, seed)
        for r in readings(drv, i < args.control_seeds):
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
