#!/usr/bin/env python3
"""Bring-up smoke run of Flare on a TPU, in one process.

    python chip_smoke.py              # one chip: handler kernels + training
    python chip_smoke.py --chips 4    # four chips: the cross-chip reduction

One chip: the four switch-handler kernels at arena widths, compiled
(``interpret=False``) and checked against their ``kernels/ref.py``
oracles; then a few steps of tinyllama-1.1b at its published widths,
cut in depth only, through the same ``launch.train.run`` path as
``python -m repro.launch.train``.

Four chips: the same training config on meshes ``4x1`` and ``2x2x1``,
wire transport against the in-network (emulated switch) one, and one
random arena through ``GradReducer`` against the summed inputs.

Exits non-zero, with no result line, when JAX finds no TPU or any check
fails.  The last line of a passing run is one JSON object naming the
device.  Step times printed on the way are smoke timings, not
measurements.
"""
import argparse
import importlib.metadata
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro import configs                     # noqa: E402  (needs src/)
from repro.launch import train                # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402

ARCH = "tinyllama-1.1b"
#: The depth cut.  The whole 22-layer step (batch 4 x seq 2048, fp32
#: params and AdamW state) needs 22.6 GB on a 15.75 GB v5e; 13 layers
#: need 15.9 GB; 12 leave about 0.5 GB by the compiler's count, 11
#: about 1.3 GB.  Widths stay as published.
N_LAYERS = 11
BATCH, SEQ = 4, 2048
#: AdamW without warmup: at 1e-3 the first steps overshoot and the loss
#: of a few steps need not fall.
LR = 1e-4
#: The handler stacks: P = 4 children, S packet slots of one 1024-byte
#: MTU (256 fp32 / 512 bf16 / 1024 int8 payload elements).
P = 4


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAIL {msg}")


def check(cond, msg: str):
    if not cond:
        fail(msg)


def device_phase(chips: int):
    import jax
    import jaxlib

    devs = jax.devices()
    d = devs[0]
    print(f"[device] jax {jax.__version__} jaxlib {jaxlib.__version__} "
          f"libtpu {importlib.metadata.version('libtpu')} | platform "
          f"{d.platform} kind {d.device_kind!r} count {len(devs)}",
          flush=True)
    check(d.platform == "tpu", f"no TPU: JAX found platform {d.platform!r}")
    check(len(devs) >= chips, f"needs {chips} chips, found {len(devs)}")
    return d


def kernel_phase():
    """Each on-path handler kernel, compiled for the chip, vs its oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    check(ops._on_tpu(), "kernel wrappers would not compile for the TPU")
    rng = np.random.default_rng(0)

    def normal(shape, dtype):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32), dtype)

    def run(name, fn, args, want, bitwise):
        text = fn.lower(*args).compile().as_text()
        check("tpu_custom_call" in text, f"{name}: no tpu_custom_call")
        got = np.asarray(fn(*args))
        want = np.asarray(want)
        check(got.shape == want.shape and got.dtype == want.dtype,
              f"{name}: {got.shape} {got.dtype} != oracle "
              f"{want.shape} {want.dtype}")
        if bitwise:
            ok = got.tobytes() == want.tobytes()
        else:
            ok = np.allclose(got, want, rtol=1e-4, atol=1e-4)
        check(ok, f"{name}: differs from its ref oracle")
        print(f"[kernel] {name} {[a.shape for a in args]} interpret=False "
              f"tpu_custom_call=yes "
              f"{'bitwise' if bitwise else 'allclose'}=ok", flush=True)

    oracle_fold = jax.jit(ref.tree_reduce)
    for dtype, s, e in [(jnp.float32, 1027, 256), (jnp.bfloat16, 1024, 512)]:
        x = normal((P, s, e), dtype)
        run(f"tree_reduce_slots {jnp.dtype(dtype).name}",
            ops.tree_reduce_slots, (x,), oracle_fold(x), bitwise=True)
    x = normal((P, 1027 * 256), jnp.float32)
    run("tree_reduce float32", ops.tree_reduce, (x,), oracle_fold(x),
        bitwise=True)

    s, e, qblock = 1029, 1024, 256
    q = jnp.asarray(rng.integers(-127, 128, size=(P, s, e)), jnp.int8)
    scales = jnp.asarray(rng.uniform(1e-3, 1e-1, size=(P, s, e // qblock))
                         .astype(np.float32))
    run("dequant_accum_slots int8", ops.dequant_accum_slots, (q, scales),
        jax.jit(ref.dequant_accum_slots)(q, scales), bitwise=False)

    b, n, size = 12, 1000, 65536
    idx = jnp.asarray(rng.integers(-1, size, size=(b, n)), jnp.int32)
    val = normal((b, n), jnp.float32)
    run("sparse_accum_slots float32",
        jax.jit(lambda i, v: ops.sparse_accum_slots(i, v, size)), (idx, val),
        jax.jit(ref.sparse_accum_slots, static_argnames=("size",))(
            idx, val, size=size), bitwise=False)


def train_args(mesh: str, steps: int, batch: int, *extra: str):
    return train._parse(["--arch", ARCH, "--steps", str(steps),
                         "--batch", str(batch), "--seq", str(SEQ),
                         "--lr", str(LR), "--mesh", mesh, *extra])


def cut_config():
    full = configs.load(ARCH).CONFIG
    return full, full.scaled(n_layers=N_LAYERS)


def train_phase(device):
    full, cfg = cut_config()
    print(f"[train] {ARCH}: d_model {cfg.d_model}, {cfg.n_heads} heads of "
          f"{cfg.hd}, kv {cfg.n_kv_heads}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab}; depth cut {full.n_layers} -> {cfg.n_layers} "
          f"layers (widths as published); batch {BATCH} x seq {SEQ}, "
          "mesh 1x1, default FlareConfig", flush=True)
    res = train.run(train_args("1x1", 6, BATCH), cfg)
    losses = res["losses"]
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    mem = res["step"].memory_analysis()
    print(f"[train] compile {res['compile_s']:.2f} s; compiler's step "
          f"memory: arguments {mem.argument_size_in_bytes} temp "
          f"{mem.temp_size_in_bytes} output {mem.output_size_in_bytes} "
          f"aliased {mem.alias_size_in_bytes} bytes", flush=True)
    print(f"[train] losses {losses}", flush=True)
    print(f"[train] peak_bytes_in_use {peak} of bytes_limit "
          f"{stats.get('bytes_limit')}", flush=True)
    print(f"[train] smoke timings, not measurements: step_s "
          f"{[round(t, 4) for t in res['step_s']]}", flush=True)
    check(len(losses) >= 5, f"took {len(losses)} steps, wants >= 5")
    check(all(math.isfinite(v) for v in losses), f"non-finite loss {losses}")
    check(abs(losses[0] - math.log(cfg.vocab)) < 1.5,
          f"first loss {losses[0]} is not near ln {cfg.vocab} = "
          f"{math.log(cfg.vocab):.2f}")
    check(losses[-1] < losses[0], f"loss does not fall: {losses}")
    check(peak is not None, "the device reports no peak_bytes_in_use")


def arena_check(mesh_arg: str, **flare):
    """One random arena through GradReducer vs the sum of the inputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as Ps

    from repro.core.engine import FlareConfig, GradReducer

    dims = tuple(int(v) for v in mesh_arg.split("x"))
    axes = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
    red_axes = axes[:-1]
    mesh = jax.make_mesh(dims, axes, axis_types=(AxisType.Auto,) * len(dims))
    world = math.prod(dims[:-1])
    rng = np.random.default_rng(1)
    shapes = [(1_000_003,), (2048, 512), (4096,)]
    xs = [rng.normal(size=(world, *s)).astype(np.float32) for s in shapes]
    reducer = GradReducer(FlareConfig(axes=red_axes, **flare))

    def body(*leaves):
        out, _ = reducer([v[0] for v in leaves])
        return tuple(o[None] for o in out)

    spec = Ps(red_axes)
    # every axis manual (model has size 1), as in the train step: the
    # switch handlers' compiled kernels cannot be auto-partitioned
    fn = jax.jit(jax.shard_map(body, in_specs=(spec,) * len(xs),
                               out_specs=(spec,) * len(xs),
                               axis_names=set(axes), check_vma=False))
    with jax.set_mesh(mesh):
        got = fn(*(jax.device_put(v, NamedSharding(mesh, spec)) for v in xs))
    for g, x in zip(got, xs):
        want = x.sum(0)
        g = np.asarray(g)
        check(all(np.allclose(g[r], want, rtol=1e-5, atol=1e-5)
                  for r in range(world)),
              f"GradReducer {flare} on {mesh_arg}: arena != sum of the "
              "per-device inputs")
    n = sum(math.prod(s) for s in shapes)
    print(f"[arena] {mesh_arg} {flare}: {n} fp32 elements "
          f"in {len(shapes)} leaves, every rank == sum of {world} inputs",
          flush=True)


def four_chip_phase():
    """The cross-chip reduction: wire against in-network, two meshes."""
    _, cfg = cut_config()
    steps, batch = 3, 4 * BATCH
    print(f"[4chip] {ARCH} depth {cfg.n_layers}, batch {batch} x seq {SEQ}, "
          f"{steps} steps per run", flush=True)
    for mesh in ("4x1", "2x2x1"):
        for repro in (True, False):
            extra = ["--reproducible"] if repro else []
            runs = {}
            for transport in ("auto", "innetwork"):
                print(f"[4chip] mesh {mesh} transport={transport} "
                      f"reproducible={repro}", flush=True)
                res = train.run(train_args(mesh, steps, batch, "--transport",
                                           transport, *extra), cfg)
                runs[transport] = res["losses"]
                check(all(math.isfinite(v) for v in res["losses"]),
                      f"non-finite loss {res['losses']}")
                if repro and transport == "innetwork":
                    # the switch's fixed-tree fold is the compiled kernel
                    check("tpu_custom_call" in res["step"].as_text(),
                          f"{mesh}: in-network step has no tpu_custom_call")
            wire, innet = runs["auto"], runs["innetwork"]
            if repro:
                check(wire == innet, f"{mesh} reproducible: in-network "
                      f"{innet} != wire {wire} bitwise")
                verdict = "bitwise equal"
            else:
                check(all(math.isclose(a, b, rel_tol=1e-4)
                          for a, b in zip(wire, innet)),
                      f"{mesh} dense: in-network {innet} vs wire {wire}")
                verdict = "equal to rel 1e-4"
            print(f"[4chip] mesh {mesh} reproducible={repro}: wire {wire} "
                  f"in-network {innet} -> {verdict}", flush=True)
        for flare in (dict(transport="auto"), dict(transport="innetwork"),
                      dict(transport="innetwork", reproducible=True)):
            arena_check(mesh, **flare)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the cross-chip phase")
    args = ap.parse_args(argv)
    enable_compile_cache()

    import jax

    device = device_phase(args.chips)
    if args.chips == 4:
        four_chip_phase()
    else:
        kernel_phase()
        train_phase(device)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
