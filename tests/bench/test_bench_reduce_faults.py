"""A reduction cell's run, with the chip check skipped and the timed path
broken underneath, comes out not correct; sound, it comes out correct.

Four CPU devices in a child process (the device count is fixed before
JAX starts).  The faults a reduction cell can have: the exchange between
chips left out, half the ranks left out with the mean taken over the
rest, an answer altered where it is produced.  The control (the
reference in bfloat16 precision) fails the cell's limit.
"""
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CASES = ["sound", "no_exchange", "half_ranks", "answer_altered"]


def _results():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "bench_reduce_cases.py"), *CASES],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    lines = [json.loads(x) for x in out.stdout.splitlines()
             if x.startswith("{")]
    return {r["case"]: r for r in lines}


def test_sound_is_correct_and_each_fault_is_not():
    res = _results()
    assert set(res) == set(CASES)
    assert res["sound"]["correct"], res["sound"]["checks"]
    assert list(res["sound"])[-1] == "checks"
    assert set(res["sound"]["metrics"]) == {"setup_s", "reduce_busbw_GBps"}
    for case in CASES[1:]:
        assert not res[case]["correct"], (case, res[case]["checks"])


def test_control_fails_the_limit():
    import jax.numpy as jnp
    import numpy as np

    from bench import spec
    from bench.drivers.reduce import reference_sum, rel_errors

    limit = spec.cell("grads-dp4.mamba2-370m").limits["limits"][
        "reduce_rel_err"]
    rng = np.random.default_rng(3)
    xs = [jnp.asarray(rng.normal(size=(4, *s)).astype(np.float32))
          for s in [(4096,), (64, 128), (7,)]]
    exact = reference_sum(xs)
    ctl = [s[None] for s in reference_sum(xs, mantissa_bits=7)]
    assert max(float(e) for e in rel_errors(ctl, exact)) > limit
    again = [s[None] for s in reference_sum(xs)]
    assert max(float(e) for e in rel_errors(again, exact)) == 0.0
