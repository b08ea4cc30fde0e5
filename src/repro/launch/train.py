"""Training driver: ``python -m repro.launch.train --arch tinyllama-1.1b``.

Runs the Flare train step (shard_map + FSDP-gather + GradReducer) on
whatever devices exist (real TPUs, or ``--fake-devices N`` CPU devices
for local bring-up), with checkpointing and failure-recovery wiring.
:func:`main` parses the command line and picks the model config;
:func:`run` trains a given ``ModelConfig`` under parsed arguments, so
other entry points (``chip_smoke.py``) reuse the same path with a cut
model.
"""
import argparse
import os
import sys


def _parse(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--mesh", type=str, default="1x1",
                    help="data x model (e.g. 4x2); pod axis via PxDxM")
    ap.add_argument("--fake-devices", type=int, default=0)
    ap.add_argument("--ckpt-dir", type=str, default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--algorithm", type=str, default="auto",
                    help="flare allreduce algorithm for replicated grads")
    ap.add_argument("--gather-algorithm", type=str, default="rhd")
    ap.add_argument("--reproducible", action="store_true")
    ap.add_argument("--compression", type=str, default="none")
    ap.add_argument("--sparse-k", type=float, default=0.0)
    ap.add_argument("--transport", type=str, default="auto",
                    choices=("auto", "innetwork"),
                    help="auto = wire collectives; innetwork = the "
                         "emulated sPIN switch data plane (repro/switch)")
    ap.add_argument("--fault-rate", type=float, default=0.0,
                    help="per-packet drop probability of the injected "
                         "lossy fabric (DESIGN.md §14; needs --transport "
                         "innetwork).  Surviving plans stay bitwise; plans "
                         "past the retry budget degrade to the wire")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed of the deterministic fault plan")
    ap.add_argument("--tenants", type=int, default=1,
                    help="run K concurrent training jobs as tenants of ONE "
                         "shared emulated switch (multi-tenant runtime, "
                         "DESIGN.md §13; implies --transport innetwork). "
                         "Tenant k cycles through dense / int8 / sparse "
                         "gradient transports")
    ap.add_argument("--partition-policy", type=str, default="weighted_fair",
                    choices=("static", "weighted_fair", "greedy"),
                    help="HPU-cluster partition policy for --tenants > 1")
    ap.add_argument("--schedule-order", type=str, default="round_robin",
                    choices=("round_robin", "priority"),
                    help="ingress interleave order for --tenants > 1")
    ap.add_argument("--congestion-replan", type=float, default=0.0,
                    metavar="HOTNESS",
                    help="after training, inject HOTNESS background load "
                         "on the fabric's first leaf slot, observe it "
                         "through the congestion monitor and re-plan the "
                         "sessions onto the cheapest tree (DESIGN.md §15; "
                         "needs --tenants > 1)")
    ap.add_argument("--trace-out", type=str, default=None, metavar="PATH",
                    help="export a Chrome-trace/Perfetto JSON timeline of "
                         "the run (flight recorder, DESIGN.md §16): "
                         "measured step spans, session lifecycle events, "
                         "the data plane's retry instants and the modeled "
                         "scheduler/perfmodel tracks, with the metric "
                         "snapshot embedded.  Summarize with "
                         "`python -m repro.obs.report`")
    ap.add_argument("--metrics-out", type=str, default=None, metavar="PATH",
                    help="export the metrics registry (typed counters/"
                         "gauges, DESIGN.md §16 name schema) as JSON")
    ap.add_argument("--health-policy", type=str, default="off",
                    choices=("off", "observe", "auto"),
                    help="run the fabric health plane after training "
                         "(DESIGN.md §17): stream the Straggler/"
                         "FaultStorm/CongestionDrift/ModelDivergence "
                         "detectors over the flight recorder and print "
                         "the incident log.  'observe' detects only; "
                         "'auto' additionally binds incidents to the "
                         "SLO policy's remediation paths (replan / "
                         "session recovery; needs --tenants > 1)")
    ap.add_argument("--incidents-out", type=str, default=None,
                    metavar="PATH",
                    help="export the health plane's incident log as "
                         "JSON (needs --health-policy; gate in CI with "
                         "`python -m repro.obs.report --incidents PATH "
                         "--fail-on critical`)")
    return ap.parse_args(argv)


def _fault_plan(args):
    """``--fault-rate/--fault-seed`` → a deterministic ``FaultPlan``
    (``None`` when no faults are requested, keeping ``FlareConfig``
    valid for the wire transports)."""
    if not args.fault_rate:
        return None
    if args.transport != "innetwork" and args.tenants <= 1:
        sys.exit("--fault-rate models the lossy switch fabric; it needs "
                 "--transport innetwork (or --tenants > 1)")
    from repro.switch.packets import FaultPlan
    return FaultPlan(seed=args.fault_seed, drop=args.fault_rate)


def _telemetry(args):
    """``--trace-out``/``--metrics-out`` → one ``repro.obs.Telemetry``
    flight recorder threaded through ``FlareConfig`` and the
    ``SessionManager`` (DESIGN.md §16); ``None`` when no artifact is
    requested — the uninstrumented run is unchanged."""
    if not (args.trace_out or args.metrics_out
            or args.health_policy != "off"):
        return None
    from repro.obs import Telemetry
    return Telemetry.create()


def _step_span(telemetry, step: int):
    """A measured span around one train step (all jobs), or a no-op."""
    if telemetry is None:
        import contextlib
        return contextlib.nullcontext()
    return telemetry.tracer.span("train.step", track="steps",
                                 args={"step": step})


def _health(args, telemetry, manager=None) -> None:
    """``--health-policy`` → one deterministic watch pass over the run's
    flight recorder (DESIGN.md §17): poll the detectors, print the
    incident log and (``auto``) the SLO policy's remediation dispatch,
    optionally exporting the log for the report CLI's ``--fail-on``
    gate."""
    if args.health_policy == "off":
        return
    from repro.obs import HealthMonitor, SLOPolicy
    from repro.obs.health import render_incidents
    monitor = None
    if manager is not None:
        from repro.runtime import CongestionMonitor
        monitor = CongestionMonitor(manager, registry=telemetry.registry)
    hm = HealthMonitor(telemetry, manager=manager, monitor=monitor)
    policy = (SLOPolicy(manager, monitor=monitor)
              if args.health_policy == "auto" else None)
    incidents, taken = hm.watch(1, policy=policy)
    print("== health ==", flush=True)
    print(render_incidents(incidents), flush=True)
    for rem in taken:
        print(f"  -> {rem.action}: "
              f"{'applied' if rem.applied else 'skipped'} "
              f"({rem.detail})", flush=True)
    if args.incidents_out:
        hm.export_incidents(args.incidents_out)
        print(f"incidents -> {args.incidents_out}", flush=True)


def _export(args, telemetry, manager=None) -> None:
    """Render the modeled timeline tracks and write the artifacts."""
    if telemetry is None:
        return
    if manager is not None:
        from repro.obs import timeline
        timeline.manager_tracks(telemetry.tracer, manager)
    if args.trace_out:
        telemetry.export_trace(args.trace_out)
        print(f"trace -> {args.trace_out}", flush=True)
    if args.metrics_out:
        telemetry.export_metrics(args.metrics_out)
        print(f"metrics -> {args.metrics_out}", flush=True)


def _run_tenants(args, mesh, mcfg, cfg, model, batch_shapes):
    """K concurrent training jobs as tenants of ONE emulated switch.

    Every job owns its own params/optimizer/data stream but all K
    ``GradReducer``s attach to a shared ``runtime.SessionManager`` — the
    multi-tenant switch runtime (DESIGN.md §13).  Tenant ``k`` cycles
    dense(f32, reproducible) / int8 / sparse transports, the
    heterogeneous mix of the acceptance scenario; after training the
    manager prints the partition/schedule/prediction report.
    """
    import time

    import jax

    from repro.core.engine import FlareConfig
    from repro.data import pipeline
    from repro.runtime import SessionManager
    from repro.train import trainer

    reduce_sizes = tuple(s for a, s in zip(mcfg.axes, mcfg.shape)
                         if a in mcfg.reduce_axes)
    telemetry = _telemetry(args)
    manager = SessionManager(mcfg.reduce_axes, reduce_sizes,
                             policy=args.partition_policy,
                             order=args.schedule_order,
                             max_sessions=max(8, 2 * args.tenants),
                             telemetry=telemetry)
    variants = [dict(reproducible=True),
                dict(compression="int8"),
                dict(sparse_k_frac=max(args.sparse_k, 0.01))]
    params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    def build(k):
        kw = variants[k % len(variants)]
        tcfg = trainer.TrainConfig(
            lr=args.lr, gather_algorithm=args.gather_algorithm,
            flare=FlareConfig(axes=mcfg.reduce_axes,
                              transport="innetwork",
                              fault_plan=_fault_plan(args),
                              telemetry=telemetry, **kw))
        return kw, trainer.jit_train_step(
            model, mesh, mcfg, tcfg, params_shapes, batch_shapes,
            donate=False, reduce_manager=manager, tenant=f"job{k}")

    jobs = []
    with jax.set_mesh(mesh):
        # phase 1 — registration traces: sessions open at *trace* time,
        # and jit is lazy, so without this pass tenant 0 would compile
        # seeing an empty switch (no contention) and earlier tenants
        # would bake stale tenant mixes into their arrival schedules.
        # An abstract eval_shape per job registers every session
        # without compiling anything.
        for k in range(args.tenants):
            _, (fn, _p, _o, _b, init_opt) = build(k)
            opt_shapes = jax.eval_shape(init_opt, params_shapes)
            jax.eval_shape(fn, params_shapes, opt_shapes, batch_shapes)
        # phase 2 — the real builds: fresh traces now see the full mix
        for k in range(args.tenants):
            kw, (fn, param_sh, opt_sh, batch_sh, init_opt) = build(k)
            params = jax.jit(model.init, out_shardings=param_sh)(
                jax.random.PRNGKey(k))
            opt = jax.jit(init_opt, out_shardings=opt_sh)(params)
            stream = pipeline.synthetic_batches(cfg, args.batch, args.seq,
                                                shardings=batch_sh,
                                                seed=100 + k)
            jobs.append({"name": f"job{k}",
                         "kind": sorted(kw)[0],
                         "fn": fn, "params": params, "opt": opt,
                         "stream": stream})
        for step in range(args.steps):
            t0 = time.time()
            line = []
            with _step_span(telemetry, step):
                for j in jobs:
                    batch = next(j["stream"])
                    j["params"], j["opt"], metrics = j["fn"](j["params"],
                                                             j["opt"],
                                                             batch)
                    line.append(f"{j['name']}({j['kind']}) "
                                f"{float(metrics['loss']):8.4f}")
            print(f"step {step:5d} | " + " | ".join(line) +
                  f" | dt {time.time() - t0:6.3f}s", flush=True)
    print(manager.report(), flush=True)
    if args.congestion_replan > 0:
        from repro.runtime import CongestionMonitor

        monitor = CongestionMonitor(
            manager,
            registry=telemetry.registry if telemetry else None)
        monitor.inject((1, 0), args.congestion_replan)
        res = manager.replan(monitor, threshold=0.5, hysteresis=0.05)
        fanins = [sorted((len(manager.tree.nodes[n].children)
                          for n in lvl), reverse=True)
                  for lvl in manager.tree.levels[1:]]
        print(f"congestion replan: replanned={res.replanned} "
              f"reason={res.reason!r} improvement_x={res.improvement_x:.3f} "
              f"readmitted={list(res.readmitted)} "
              f"evicted={list(res.evicted)} fanins={fanins}", flush=True)
        print(manager.report(), flush=True)
    _health(args, telemetry, manager)
    _export(args, telemetry, manager)


def main(argv=None):
    args = _parse(argv)
    if args.fake_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.fake_devices}")
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax.numpy as jnp

    from repro import configs
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    mod = configs.load(args.arch)
    cfg = (mod.SMOKE if args.smoke else mod.CONFIG)
    if args.smoke:
        cfg = cfg.scaled(dtype=jnp.float32)
    run(args, cfg)


def run(args, cfg) -> dict | None:
    """Train ``cfg`` as the parsed ``args`` say, on ``jax.devices()``.

    Returns ``{"step", "compile_s", "losses", "step_s"}`` for a single
    job (the compiled step program and its compile time, then one loss
    and one host-clock step time per step); ``None`` for
    ``--tenants > 1``.
    """
    import time

    import jax
    from jax.sharding import AxisType

    from repro.core.engine import FlareConfig
    from repro.data import pipeline
    from repro.ft import CheckpointManager
    from repro.models import get_model
    from repro.sharding import rules
    from repro.train import trainer

    dims = [int(x) for x in args.mesh.split("x")]
    if len(dims) == 2:
        axes, shape = ("data", "model"), tuple(dims)
    elif len(dims) == 3:
        axes, shape = ("pod", "data", "model"), tuple(dims)
    else:
        sys.exit("--mesh must be DxM or PxDxM")
    mesh = jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(shape))
    mcfg = rules.MeshCfg(axes, shape)
    model = get_model(cfg)

    key = jax.random.PRNGKey(0)
    params_shapes = jax.eval_shape(model.init, key)
    batch0 = next(pipeline.synthetic_batches(cfg, args.batch, args.seq,
                                             prefetch=False))
    batch_shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), batch0)

    if args.congestion_replan > 0 and args.tenants <= 1:
        sys.exit("--congestion-replan re-plans the shared switch's "
                 "sessions; it needs --tenants > 1")
    if args.health_policy == "auto" and args.tenants <= 1:
        sys.exit("--health-policy auto binds remediations to the shared "
                 "switch's SessionManager; it needs --tenants > 1 "
                 "(use --health-policy observe for a single job)")
    if args.incidents_out and args.health_policy == "off":
        sys.exit("--incidents-out exports the health plane's log; it "
                 "needs --health-policy observe|auto")

    if args.tenants > 1:
        # branch before the single-job FlareConfig: the tenants path
        # builds its own innetwork configs (a --fault-rate without
        # --transport innetwork is valid there and would fail the
        # single-job validation below)
        _run_tenants(args, mesh, mcfg, cfg, model, batch_shapes)
        return None

    telemetry = _telemetry(args)
    tcfg = trainer.TrainConfig(
        lr=args.lr,
        gather_algorithm=("fixed_tree" if args.reproducible
                          else args.gather_algorithm),
        flare=FlareConfig(axes=mcfg.reduce_axes, algorithm=args.algorithm,
                          reproducible=args.reproducible,
                          compression=args.compression,
                          sparse_k_frac=args.sparse_k,
                          transport=args.transport,
                          fault_plan=_fault_plan(args),
                          telemetry=telemetry))

    result = {"losses": [], "step_s": []}
    with jax.set_mesh(mesh):
        fn, param_sh, opt_sh, batch_sh, init_opt = trainer.jit_train_step(
            model, mesh, mcfg, tcfg, params_shapes, batch_shapes,
            donate=True)
        # initialise in place, already sharded: an eager init would
        # materialise the whole model on the first device
        params = jax.jit(model.init, out_shardings=param_sh)(key)
        opt = jax.jit(init_opt, out_shardings=opt_sh)(params)

        start = 0
        cm = None
        if args.ckpt_dir:
            cm = CheckpointManager(args.ckpt_dir)
            if args.resume and cm.latest_step() is not None:
                start = cm.latest_step()
                state = cm.restore(start, {"p": params, "o": opt},
                                   {"p": param_sh, "o": opt_sh})
                params, opt = state["p"], state["o"]
                print(f"resumed from step {start}")

        t0 = time.perf_counter()
        batch_in = jax.tree.map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            batch_shapes, batch_sh)
        step_fn = fn.lower(params, opt, batch_in).compile()
        result["step"] = step_fn
        result["compile_s"] = time.perf_counter() - t0
        print(f"compiled train step in {result['compile_s']:.1f}s",
              flush=True)

        stream = pipeline.synthetic_batches(cfg, args.batch, args.seq,
                                            shardings=batch_sh, seed=1)
        for step in range(start, args.steps):
            t0 = time.perf_counter()
            batch = next(stream)
            with _step_span(telemetry, step):
                params, opt, metrics = step_fn(params, opt, batch)
                loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            result["losses"].append(loss)
            result["step_s"].append(dt)
            print(f"step {step:5d} loss {loss:8.4f} "
                  f"gnorm {float(metrics['grad_norm']):8.3f} "
                  f"dt {dt:6.3f}s", flush=True)
            if cm and args.ckpt_every and (step + 1) % args.ckpt_every == 0:
                cm.save(step + 1, {"p": params, "o": opt})
        if cm:
            cm.wait()
    _health(args, telemetry)
    _export(args, telemetry)
    return result


if __name__ == "__main__":
    main()
