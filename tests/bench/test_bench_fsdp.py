"""The FSDP training cell on four CPU devices, at a tiny size: its run
comes out correct, and the same step with half of its batch, or with its
gradient exchange between the chips left out, does not.

Four devices need their own process (``XLA_FLAGS`` before JAX starts),
so the checks run in one child and report back as JSON."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CHILD = r'''
import dataclasses, json, time
import jax
from bench import run as bench_run, spec
from bench_tiny import tiny_cell

# float32 compute: the cell's limits are set for its 48 layers of 1024,
# whose bfloat16 noise over 52 rows is smaller than a tiny model's
cell = tiny_cell("train-fsdp4.mamba2-370m", seq_len=128, batch_per_chip=2,
                 batch_pool=8)
cell = dataclasses.replace(cell, config=dict(cell.config,
                                             compute_dtype="float32"))
drv = spec.driver(cell.driver).Driver(cell, 2**33 + 7)
out = {"driver": cell.driver, "chips": cell.chips,
       "devices": len(jax.devices()), "world": drv.world}
drv.free()


def correct():
    return bench_run.run_cell(cell, 2**33 + 5, 0.2, False,
                              time.perf_counter())["correct"]


out["sound"] = correct()

from repro.models import mamba2
real = mamba2.loss_fn
def half(cfg, params, batch, **kw):
    n = batch["tokens"].shape[0] // 2
    return real(cfg, params, {k: v[:n] for k, v in batch.items()}, **kw)
mamba2.loss_fn = half
out["half_batch"] = correct()
mamba2.loss_fn = real

# the exchange left out: the replicated leaves keep each chip's own
# gradient, and each FSDP shard is its slice of the chip's own gradient
from repro.core import collectives, engine
def own_slice(x, axes, **kw):
    n = jax.lax.axis_size(axes[-1])
    return jax.lax.dynamic_slice_in_dim(
        x, jax.lax.axis_index(axes[-1]) * (x.shape[0] // n),
        x.shape[0] // n)
collectives.reduce_scatter = own_slice
engine.GradReducer.__call__ = lambda self, grads, state=None: (grads, state)
out["no_exchange"] = correct()
print(json.dumps(out))
'''


@pytest.fixture(scope="module")
def child():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [ROOT, os.path.join(ROOT, "src"),
                    os.path.join(ROOT, "tests", "bench")]))
    res = subprocess.run([sys.executable, "-c", CHILD], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-4000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_fsdp_cell_runs_on_four_devices(child):
    assert (child["driver"], child["chips"], child["devices"],
            child["world"]) == ("train", 4, 4, 4)
    assert child["sound"] is True


def test_fsdp_cell_with_half_the_batch_is_not_correct(child):
    assert child["half_batch"] is False


def test_fsdp_cell_without_the_exchange_between_chips_is_not_correct(child):
    assert child["no_exchange"] is False
