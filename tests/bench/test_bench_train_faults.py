"""A training cell's run, with the chip check skipped and the timed path
broken underneath, comes out not correct; sound, it comes out correct.

Tiny model on the CPU (``bench_tiny``), the cell's own limits, driver
and comparison.  The faults a one-chip training cell can have: a step
that returns its state unchanged, and half of the batch left out with
the mean taken over the rest.
"""
import time

import jax.numpy as jnp
import pytest
from bench_tiny import tiny_cell

from bench import run as bench_run

WORKLOAD = "train-1chip.mamba2-370m"
SEED = 2**33 + 5


def _run():
    return bench_run.run_cell(tiny_cell(WORKLOAD), SEED, 0.2, False,
                              time.perf_counter())


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"setup_s", "tokens_per_s"}


def _state_unchanged(monkeypatch):
    from repro.train import optim

    def update(params, grads, opt_state, **kw):
        return params, dict(opt_state, step=opt_state["step"] + 1)
    monkeypatch.setattr(optim, "adamw_update", update)


def _half_batch(monkeypatch):
    from repro.models import mamba2
    real = mamba2.loss_fn

    def loss_fn(cfg, params, batch, **kw):
        half = batch["tokens"].shape[0] // 2
        return real(cfg, params, {k: v[:half] for k, v in batch.items()},
                    **kw)
    monkeypatch.setattr(mamba2, "loss_fn", loss_fn)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_broken_step_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    res = _run()
    assert not res["correct"], res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_control_in_the_programs_place_is_not_correct():
    """The control (every matmul's operands rounded to float8_e4m3fn)
    fails the cell's limits where the bf16 program passes them."""
    from bench import compare, spec
    from bench.reference import train as ref_train

    cell = tiny_cell(WORKLOAD)
    drv = spec.driver(cell.driver).Driver(cell, SEED)
    prog = drv.program_readings()
    drv.free()
    ref = drv.reference()
    ctl = drv.reference(dot=ref_train.lowered_dot(jnp.float8_e4m3fn))
    limits = cell.limits["limits"]
    assert compare.passed(compare.checks(drv.gaps(prog, ref), limits))
    assert not compare.passed(compare.checks(drv.gaps(ctl, ref), limits))
