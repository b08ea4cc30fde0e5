#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted as ``setup_s``, from the start of this process): the
cell's program compiled or read from JAX's persistent cache, its weights
and inputs made on the device from ``--seed``, every shape of the window
run once.  Then the window: ``--seconds`` of the cell's closed loop.
With ``--trace 1`` the window runs under the JAX profiler and the result
holds the cell's per-layer metrics, read from that trace; with
``--trace 0``, its end-to-end metrics.  Once the window has closed, the
program's state is freed and the plain reference decides ``correct``.

The last line of standard output is one JSON object.  A machine whose
JAX finds no TPU, or fewer chips than the cell asks for, gets an error
and a non-zero exit, and no result.
"""
import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import shutil        # noqa: E402
import sys           # noqa: E402
import tempfile      # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class CompileCounter:
    """Counts backend compiles while ``on`` (the measured window)."""

    def __init__(self):
        self.on = False
        self.count = 0

    def __call__(self, event, duration, **kw):
        if self.on and event == COMPILE_EVENT:
            self.count += 1


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def run_cell(cell, seed: int, seconds: float, traced: bool,
             t_start: float, counter: CompileCounter | None = None,
             root=None) -> dict:
    """One run of ``cell``; returns the result object.  ``root`` is the
    checkout whose ``bench/metrics`` hold the readers."""
    import jax

    from bench import compare, spec, trace

    counter = counter or CompileCounter()
    drv = spec.driver(cell.driver).Driver(cell, seed)
    setup_s = time.perf_counter() - t_start
    log(f"{cell.workload} seed {seed}: setup_s {setup_s!r}")

    tr = None
    counter.on = True
    if traced:
        logdir = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            jax.profiler.start_trace(logdir)
            with jax.profiler.TraceAnnotation("bench.window"):
                counts = drv.window(seconds)
            jax.profiler.stop_trace()
            counter.on = False
            tr = trace.load(trace.find_xplane(logdir))
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
    else:
        counts = drv.window(seconds)
    counter.on = False
    log(f"window {counts['window_s']!r} s, {counts['attempted']} attempted, "
        f"{counts['failed']} failed, {counter.count} compiles in the window")

    devices = jax.devices()[:cell.chips]
    device = devices[0]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    metrics = {}
    if traced:
        ctx = {"cell": cell, "counts": counts, "trace": tr,
               "chips": cell.chips, "peaks": spec.peaks(device.device_kind)}
        for m in cell.per_layer:
            v = spec.reader(m["name"], root or spec.ROOT).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = drv.end_to_end(counts)
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    drv.free()
    values = drv.check()
    chk = compare.checks(values, cell.limits["limits"])
    result = {
        "correct": compare.passed(chk) and counts["failed"] == 0,
        "attempted": counts["attempted"],
        "failed": counts["failed"],
        "metrics": metrics,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": peak,
                   "compiled_bytes": drv.compiled_bytes},
    }
    if traced:
        result["device"]["busy_s"] = trace.busy_s(tr)
        result["device"]["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": trace.top_ops(tr),
                               "idle_gaps": trace.idle_gaps(tr)}
    result["checks"] = chk
    for line in compare.render(chk):
        print(line, file=sys.stderr, flush=True)
    return result


def main(argv=None):
    args = parse(argv)
    from bench import spec

    cell = spec.cell(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench: no TPU: JAX found platform {devices[0].platform!r}")
    if len(devices) < cell.chips:
        sys.exit(f"bench: {cell.workload} needs {cell.chips} chips, JAX "
                 f"found {len(devices)}")

    from repro.launch.cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"compile cache {cache}")
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      T_START, counter)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
