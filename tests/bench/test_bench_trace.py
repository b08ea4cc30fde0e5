"""The reduction from a profiler trace to busy time, op time and gaps:
by hand on a made-up trace, and on one training step recorded on a TPU
v5e (``data/train-1chip.1step.xplane.pb.gz``: train-1chip.mamba2-370m,
``--seconds 1 --trace 1``)."""
import os

import pytest

from bench import trace
from bench.trace import Op, Span, Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _made_up() -> Trace:
    # window 0..100 ns.  Chip 0: a loop 5..60 holding a fusion 10..30
    # and a collective 30..50; an async collective in flight 20..55; a
    # copy 70..80.  Chip 1: one op 0..100 that spills out of the window.
    d0 = trace.nest([("while.1", 5, 55), ("fusion.2", 10, 20),
                     ("all-reduce-done.3", 30, 20), ("copy.4", 70, 10)])
    d0.append(Op("all-reduce-start.3", 20, 35, 0.0, False, True))
    d1 = trace.nest([("fusion.9", -20, 140)])
    spans = [Span("bench.window", 0, 100), Span("bench.step", 0, 60),
             Span("bench.loss_read", 55, 100)]
    return Trace({"/device:TPU:0": d0, "/device:TPU:1": d1}, spans, 0, 100)


def test_nesting_gives_self_time_and_leaves():
    ops = {o.name: o for o in trace.nest(
        [("while.1", 5, 55), ("fusion.2", 10, 20), ("copy.4", 70, 10)])}
    assert ops["while.1"].self_ns == 35 and not ops["while.1"].leaf
    assert ops["fusion.2"].self_ns == 20 and ops["fusion.2"].leaf
    assert trace.op_name("%fusion.896 = (f32[12]) fusion(f32[12] %x)") \
        == "fusion.896"


def test_busy_idle_and_op_time_by_hand():
    tr = _made_up()
    # chip 0 leaves: 10..50 and 70..80 = 50 ns; chip 1: 100 ns
    assert trace.busy_s(tr) == pytest.approx(75e-9)
    assert trace.idle_percent(tr) == pytest.approx(25.0)
    # chip 0: the done 30..50 and the in-flight start 20..55 → 20..55
    assert trace.collective_seconds(tr) == pytest.approx(17.5e-9)
    assert trace.self_seconds(tr, collective=True) == pytest.approx(10e-9)
    # chip 0: loop 15 + fusion 20 + copy 10; chip 1: 140 clipped to 100
    assert trace.self_seconds(tr, collective=False) == pytest.approx(72.5e-9)


def test_top_ops_and_idle_gaps_by_hand():
    tr = _made_up()
    top = dict(trace.top_ops(tr))
    assert top["fusion"] == pytest.approx(60e-9)
    assert top["while"] == pytest.approx(7.5e-9)
    gaps = trace.idle_gaps(tr)
    # chip 0's gaps: 50..70 (midpoint 60: loss_read), 80..100
    # (loss_read), 0..10 (step)
    assert [g[0] for g in gaps] == ["bench.loss_read", "bench.loss_read",
                                    "bench.step"]
    assert [g[1] for g in gaps] == pytest.approx([20e-9, 20e-9, 10e-9])


def test_union():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_recorded_training_step():
    tr = trace.load(os.path.join(DATA, "train-1chip.1step.xplane.pb.gz"))
    assert list(tr.devices) == ["/device:TPU:0"]
    assert {s.name for s in tr.spans} == {
        "bench.window", "bench.next_batch", "bench.step", "bench.loss_read"}
    assert tr.window_s == pytest.approx(1.983356657)
    busy = trace.busy_s(tr)
    assert busy == pytest.approx(1.94323688)
    assert trace.idle_percent(tr) == pytest.approx(2.0228221111116)
    assert 0 < busy < tr.window_s
    # the one-chip FSDP gather still sends to itself: collective-permutes
    # in flight for about half the step, overlapped with compute
    assert trace.collective_seconds(tr) == pytest.approx(1.012654502)
    assert trace.self_seconds(tr, collective=True) == pytest.approx(0.011737815)
    assert trace.self_seconds(tr, collective=False) == pytest.approx(1.968429053)
    top = trace.top_ops(tr)
    assert [name for name, _ in top[:3]] == ["fusion", "copy", "reduce-window"]
    assert top[0][1] == pytest.approx(0.909452147)
    gaps = trace.idle_gaps(tr, 4)
    assert [g[0] for g in gaps] == ["bench.loss_read"] * 4
    assert gaps[0][1] == pytest.approx(0.003178376)
