"""Device time by the program's ``jax.named_scope``s: the scope paths of
a compiled module, and the self time under each scope on a made-up
trace; the recorded training step (``data/train-1chip.1step``) still
gives the readers that split by op kind the values they gave."""
import os

import jax
import jax.numpy as jnp
import pytest

from bench import scopes, spec, trace
from bench.trace import Op, Span, Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_op_scopes_of_a_scanned_rematted_vmapped_grad():
    def layer(h, w):
        with jax.named_scope("mamba2.inner"):
            return jnp.tanh(h @ w), None

    def one(x, ws):
        with jax.named_scope("lm.outer"):
            h, _ = jax.lax.scan(jax.remat(layer), x, ws)
        return jnp.sum(h ** 2)

    def loss(ws, xs):
        return jnp.sum(jax.vmap(one, in_axes=(0, None))(xs, ws))

    ws, xs = jnp.ones((3, 8, 8)), jnp.ones((2, 4, 8))
    text = jax.jit(jax.grad(loss)).lower(ws, xs).compile().as_text()
    s = scopes.op_scopes(text)
    assert s.module == "jit_loss" and s.scoped
    # every matrix product, forward, recompute and backward, is the layer's
    dots = [line.split(" = ")[0].strip().removeprefix("ROOT ").lstrip("%")
            for line in text.splitlines() if " dot(" in line]
    assert dots
    for name in dots:
        path = s.paths[name]
        assert scopes.under(path, "mamba2.inner"), path
        assert scopes.under(path, "lm.outer"), path
    backward = [p for p in s.paths.values() if "transpose(" in p
                and scopes.under(p, "mamba2.inner")]
    assert backward
    # a fusion is found under its own name, as the trace names it
    fusions = [n for n in s.paths if "fusion" in n]
    assert fusions and all(s.paths[n] for n in fusions)


HLO = """HloModule jit_step_body, is_scheduled=true

FileNames
1 "model.py"

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  %multiply.1 = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(step_body)/jvp(mamba2.ssd)/mul" stack_frame_id=1}
  ROOT %convert.2 = f32[4]{0} convert(%multiply.1)
}

%body.3 (p.4: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p.4 = (s32[], f32[4]{0}) parameter(0)
  %get-tuple-element.5 = f32[4]{0} get-tuple-element(%p.4), index=1
  %dynamic-update-slice.6 = f32[4]{0} dynamic-update-slice(%get-tuple-element.5, %get-tuple-element.5, %c.7)
  ROOT %tuple.8 = (s32[], f32[4]{0}) tuple(%c.7, %dynamic-update-slice.6)
}

ENTRY %main.9 (x: f32[4]) -> (f32[4]) {
  %x = f32[4]{0} parameter(0), metadata={op_name="x"}
  %fusion.10 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %copy.11 = f32[4]{0} copy(%fusion.10)
  %add.12 = f32[4]{0} add(%copy.11, %copy.11), metadata={op_name="jit(step_body)/transpose(jvp(flare.pack))/add;jit(step_body)/flare.unpack/add"}
  %add.13 = f32[4]{0} add(%add.12, %add.12), metadata={op_name="jit(step_body)/neg;jit(step_body)/flare.unpack/add"}
  %negate.14 = f32[4]{0} negate(%x)
  %broadcast.15 = f32[4]{0} broadcast(%c.7), dimensions={}
  %tuple.16 = (s32[], f32[4]{0}) tuple(%x, %broadcast.15)
  %while.17 = (s32[], f32[4]{0}) while(%tuple.16), condition=%cond.18, body=%body.3
  %get-tuple-element.19 = f32[4]{0} get-tuple-element(%while.17), index=1
  %subtract.20 = f32[4]{0} subtract(%get-tuple-element.19, %add.13), metadata={op_name="jit(step_body)/flare.ring.all_gather/while/sub"}
  ROOT %tuple.21 = (f32[4]{0}) tuple(%subtract.20)
}
"""


def test_op_scopes_by_hand():
    s = scopes.op_scopes(HLO)
    assert s.module == "jit_step_body"
    # a fusion without metadata takes its root's, which takes its operand's
    assert s.paths["fusion.10"] == "jit(step_body)/jvp(mamba2.ssd)/mul"
    # an op the compiler made takes its first operand's
    assert s.paths["copy.11"] == s.paths["fusion.10"]
    # merged metadata: the first path, or the first under a program scope
    assert s.paths["add.12"] == "jit(step_body)/transpose(jvp(flare.pack))/add"
    assert s.paths["add.13"] == "jit(step_body)/flare.unpack/add"
    assert s.paths["negate.14"] == "x"
    # a loop without metadata is named by its first user, and names the
    # ops of its body that have none; a buffer of zeros takes its user's,
    # through the tuple that hands it to the loop
    loop = "jit(step_body)/flare.ring.all_gather/while/sub"
    for name in ("while.17", "dynamic-update-slice.6", "get-tuple-element.5",
                 "tuple.16", "broadcast.15"):
        assert s.paths[name] == loop, name
    assert scopes.components(s.paths["add.12"]) == (
        "step_body", "flare.pack", "add")
    assert scopes.under(s.paths["add.12"], "flare.")
    assert not scopes.under(s.paths["add.12"], "flare.unpack")


def _made_up() -> tuple[Trace, scopes.OpScopes]:
    # window 0..100 ns.  Chip 0: a loop 0..60 (the layer) holding an
    # SSD fusion 10..30 and a backward SSD fusion 30..50; an async
    # collective in flight 20..55 (never self time); a pack copy
    # 70..80; an op the module does not hold 80..90.  Chip 1: a head
    # fusion -20..40 that spills out of the window, an unscoped
    # negate 40..50.
    d0 = trace.nest([("while.1", 0, 60), ("fusion.2", 10, 20),
                     ("fusion.3", 30, 20), ("copy.4", 70, 10),
                     ("mystery.5", 80, 10)])
    d0.append(Op("all-reduce-start.6", 20, 35, 0.0, False, True))
    d1 = trace.nest([("fusion.7", -20, 60), ("negate.8", 40, 10)])
    tr = Trace({"/device:TPU:0": d0, "/device:TPU:1": d1},
               [Span("bench.window", 0, 100)], 0, 100)
    s = scopes.OpScopes("jit_step_body", {
        "while.1": "jit(step_body)/jvp(mamba2.layer)/while",
        "fusion.2": "jit(step_body)/jvp(mamba2.layer)/while/body/"
                    "mamba2.ssd/dot_general",
        "fusion.3": "jit(step_body)/transpose(jvp(mamba2.layer))/while/"
                    "body/remat(mamba2.ssd)/dot_general",
        "copy.4": "jit(step_body)/flare.pack/dynamic_update_slice",
        "all-reduce-start.6": "jit(step_body)/flare.psum/psum",
        "fusion.7": "jit(step_body)/jvp(lm.head_loss)/dot_general",
        "negate.8": "jit(step_body)/neg",
    })
    return tr, s


def test_scope_seconds_and_unscoped_share_by_hand():
    tr, s = _made_up()
    # chip 0: the two SSD fusions, 40 ns; averaged over two chips
    assert scopes.scope_seconds(tr, s, "mamba2.ssd") == pytest.approx(20e-9)
    # the layer: its loop's self time (20) and both fusions (40)
    assert scopes.scope_seconds(tr, s, "mamba2.layer") == \
        pytest.approx(30e-9)
    assert scopes.scope_seconds(tr, s, "flare.pack") == pytest.approx(5e-9)
    # a prefix: every flare scope; the async psum has no self time
    assert scopes.scope_seconds(tr, s, "flare.") == pytest.approx(5e-9)
    # chip 1: the head fusion clipped to its 40 ns in the window
    assert scopes.scope_seconds(tr, s, "lm.head_loss") == \
        pytest.approx(20e-9)
    assert scopes.scope_seconds(tr, s, "train.adamw") == 0.0
    # self time 130 ns (chip 0: 60 + 10 + 10; chip 1: 40 + 10), of
    # which the unknown op (10) and the unscoped negate (10) are unscoped
    share = scopes.unscoped_share(tr, s)
    assert share == pytest.approx(100 * 20 / 130)
    # the scoped and unscoped parts add up to all the self time
    scoped = sum(scopes.scope_seconds(tr, s, x) for x in
                 ("mamba2.layer", "flare.", "lm.head_loss"))
    assert scoped / (1 - share / 100) == pytest.approx(
        trace.self_seconds(tr, collective=False))


def test_of_run_takes_the_live_program_that_ran(monkeypatch):
    """Without ``ctx["op_scopes"]`` the program is found among the
    compiled programs alive in the process: the one whose op names cover
    the window, if they cover at least half of it."""
    def step(x):
        with jax.named_scope("train.adamw"):
            return jnp.tanh(x) * 2

    ours = jax.jit(step).lower(jnp.ones(8)).compile()
    assert any(c is ours for c in scopes._live_programs())
    other = jax.jit(lambda x: jnp.sum(x)).lower(jnp.ones(8)).compile()
    s = scopes.op_scopes(ours.as_text())
    names = [n for n, p in s.paths.items() if scopes.under(p, "train.adamw")]
    assert names
    monkeypatch.setattr(scopes, "_live_programs", lambda: [other, ours])

    def ctx_of(events):
        tr = Trace({"/device:TPU:0": trace.nest(events)},
                   [Span("bench.window", 0, 1000)], 0, 1000)
        return {"trace": tr, "counts": {"steps": 1}}

    ctx = ctx_of([(n, 10 * i, 10) for i, n in enumerate(names)])
    got = spec.reader("train.optimizer_ms_per_step").read(ctx)
    assert got == pytest.approx(1e3 * 10e-9 * len(names))
    assert ctx["op_scopes"].module == s.module
    # a window mostly of ops no live program holds reads nothing
    ctx = ctx_of([(names[0], 0, 10), ("mystery.1", 10, 100)])
    assert spec.reader("train.optimizer_ms_per_step").read(ctx) is None


READS = {
    "reduce.pack_ms_per_call": (("flare.pack",), "calls"),
    "reduce.reduce_scatter_ms_per_call": (("flare.ring.reduce_scatter",),
                                          "calls"),
    "reduce.all_gather_ms_per_call": (("flare.ring.all_gather",), "calls"),
    "reduce.unpack_ms_per_call": (("flare.unpack",), "calls"),
    "train.ssd_ms_per_step": (("mamba2.ssd",), "steps"),
    "train.head_ms_per_step": (("lm.head_loss",), "steps"),
    "train.optimizer_ms_per_step": (("train.clip", "train.adamw"), "steps"),
    "train.flare_ms_per_step": (("flare.",), "steps"),
}


@pytest.mark.parametrize("metric", sorted(READS))
def test_scoped_readers_on_a_made_up_trace(metric):
    tr, s = _made_up()
    names, count = READS[metric]
    ctx = {"trace": tr, "op_scopes": s, "counts": {count: 4}}
    want = 1e3 * sum(scopes.scope_seconds(tr, s, n) for n in names) / 4
    assert spec.reader(metric).read(ctx) == pytest.approx(want)
    # a program that carries none of the scopes reads nothing
    bare = scopes.OpScopes("jit_f", {k: "jit(f)/mul" for k in s.paths})
    assert spec.reader(metric).read(dict(ctx, op_scopes=bare)) is None


@pytest.mark.parametrize("metric", ["device.unscoped_share.reduce",
                                    "device.unscoped_share.train"])
def test_unscoped_readers_on_a_made_up_trace(metric):
    tr, s = _made_up()
    ctx = {"trace": tr, "op_scopes": s, "counts": {}}
    assert spec.reader(metric).read(ctx) == pytest.approx(100 * 20 / 130)
    assert spec.reader(metric).read(dict(ctx, trace=None)) is None


def test_recorded_step_reads_as_before():
    """The recorded step's program (a parent's, with no scopes) is not
    alive here: the readers that split by op kind read what they read,
    and the scoped readers read nothing, without raising."""
    tr = trace.load(os.path.join(DATA, "train-1chip.1step.xplane.pb.gz"))
    ctx = {"trace": tr, "counts": {"steps": 1, "calls": 1}}
    read = {m: spec.reader(m).read(dict(ctx)) for m in
            [m["name"] for m in spec.benchmark()["per_layer"]
             if m["source"] == "device_trace"]}
    assert read["device.idle_share.train"] == pytest.approx(2.0228221111116)
    assert read["device.idle_share.reduce"] == pytest.approx(2.0228221111116)
    assert read["reduce.local_ms_per_call"] == pytest.approx(1968.429053)
    assert read["reduce.wire_ms_per_call"] == pytest.approx(1012.654502)
    assert [m for m, v in read.items() if v is not None] == [
        "device.idle_share.train", "reduce.wire_ms_per_call",
        "reduce.local_ms_per_call", "device.idle_share.reduce"]
    assert trace.top_ops(tr)[0][1] == pytest.approx(0.909452147)
