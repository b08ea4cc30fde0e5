"""DeepSeek-V2-Lite: the program against the plain reference
(``bench/reference/deepseek_v2.py``), the share of the experts held, the
``train_ref`` driver, the FLOP counts of ``bench/flops_moe.py`` and the
new metric readers, at small sizes on the CPU."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops_moe, scopes, spec, trace, traffic, weights
from bench import run as bench_run
from bench.drivers import train_ref
from bench.drivers.train import model_config, param_shapes
from bench.reference import deepseek_v2 as ref
from bench.trace import Span, Trace

WORKLOAD = "train-1chip-8k.deepseek-v2-lite"
SEED = 2**35 + 11
#: the cell's program (MLA with YaRN and the latent norm, top-k over all
#: experts without renormalising, held experts, the balance loss) at a
#: CPU size
TINY = dict(n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
            d_ff=128, vocab=256, n_experts=16, experts_held=4,
            experts_per_token=3, moe_d_ff=32, n_shared_experts=2,
            mla_kv_lora=32, mla_qk_nope=16, mla_qk_rope=8, mla_v_dim=16,
            attn_chunk=32, moe_chunk=64)


def tiny_program(**kw) -> dict:
    return dict(spec.cell(WORKLOAD).config["program"], **dict(TINY, **kw))


def _model(prog: dict, seed: int = SEED):
    from repro.models import get_model
    model = get_model(model_config({"program": prog,
                                    "compute_dtype": "float32"}))
    params = weights.init_params(param_shapes(model),
                                 weights.key_from_seed(seed))
    return model, params


def _batch(rows=2, seq=128):
    return traffic.token_batch(jax.random.PRNGKey(1), 0, vocab=250,
                               batch=rows, seq=seq, zipf_s=1.3)


def test_program_loss_and_every_gradient_equal_the_reference():
    prog = tiny_program()
    model, params = _model(prog)
    b = _batch()
    with jax.default_matmul_precision("highest"):
        (lp, counters), gp = jax.value_and_grad(model.loss_aux, has_aux=True)(
            params, b)
    lr, gr = jax.value_and_grad(
        lambda p: ref.loss(prog, p, b["tokens"], b["labels"]))(params)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    flat_p = jax.tree_util.tree_flatten_with_path(gp)[0]
    flat_r = jax.tree.leaves(gr)
    assert len(flat_p) == len(flat_r) == len(jax.tree.leaves(params))
    for (path, a), r in zip(flat_p, flat_r):
        a, r = np.asarray(a), np.asarray(r)
        scale = max(np.abs(r).max(), 1e-6)
        assert np.abs(a - r).max() <= 2e-4 * scale, jax.tree_util.keystr(path)
    # the rows the program counts are the reference's own count
    _, _, rows = ref.hidden(prog, params, b["tokens"])
    assert int(counters["moe_rows"]) == int(rows) > 0


def _moe_params(prog, seed=SEED):
    _, params = _model(prog, seed)
    return jax.tree.map(lambda a: a[0], params["layers"]["moe"]["ffn"])


def test_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Four chips holding four experts each: each share's partial output
    (its experts first in the router's order), with the shared experts
    counted once, sums to the reference's layer over all 16 experts."""
    from repro.models import base
    full = tiny_program(experts_held=16)
    p = _moe_params(full)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 64, TINY["d_model"]))
    held = 4
    cfg = model_config({"program": tiny_program(experts_held=held),
                        "compute_dtype": "float32"})
    with jax.default_matmul_precision("highest"):
        parts = []
        for j in range(16 // held):
            pj = dict(p, router=jnp.roll(p["router"], -j * held, axis=1))
            for n in ("w_gate", "w_up", "w_down"):
                pj[n] = p[n][j * held:(j + 1) * held]
            parts.append(base.moe_dropless(cfg, pj, x)[0])
        shared = base.swiglu(p["shared"], x.reshape(-1, TINY["d_model"]))
        total = sum(parts) - (len(parts) - 1) * shared.reshape(x.shape)
    want, _, rows = ref.moe(full, p, x, ref.exact_dot)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert int(rows) == 2 * 64 * TINY["experts_per_token"]


def test_dropless_under_skew_matches_the_reference_and_drops_nothing():
    """Tokens that lean one way send every one of them to the same
    experts, far past the capacity path's 1.25 · T·k/E: the capacity path
    drops some, the dropless layer matches the reference and its
    ``moe.dropped`` counter stays 0."""
    from repro.models import base
    from repro.obs.metrics import MetricsRegistry, observe_moe

    prog = tiny_program(experts_held=16)
    p = _moe_params(prog)
    d = TINY["d_model"]
    lean = jax.random.normal(jax.random.PRNGKey(6), (d,))
    x = 3.0 * lean + 0.3 * jax.random.normal(jax.random.PRNGKey(7),
                                            (2, 64, d))
    t, k, e = 128, TINY["experts_per_token"], TINY["n_experts"]
    cfg = model_config({"program": prog, "compute_dtype": "float32"})
    with jax.default_matmul_precision("highest"):
        probs = jax.nn.softmax(x.reshape(t, d) @ p["router"], -1)
        load = jnp.bincount(jax.lax.top_k(probs, k)[1].reshape(-1), length=e)
        assert int(load.max()) > max(1.25 * t * k / e, 32)
        out, stats = base.moe_dropless(cfg, p, x)
        # the capacity path (renormalised gates) drops at 1.25, not at 100
        renorm = dataclasses.replace(cfg, norm_topk_prob=True)
        wide = base.moe_block(dataclasses.replace(renorm,
                                                  capacity_factor=100.0), p, x)
        tight = base.moe_block(dataclasses.replace(renorm,
                                                   capacity_factor=1.25), p, x)
        np.testing.assert_allclose(
            np.asarray(base.moe_dropless(renorm, p, x)[0]), np.asarray(wide),
            rtol=1e-4, atol=1e-5)
        assert np.abs(np.asarray(tight - wide)).max() > 1e-2
    want, aux, rows = ref.moe(prog, p, x, ref.exact_dot)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-4,
                               atol=1e-5)
    assert float(stats["aux"]) == pytest.approx(float(aux), rel=1e-5)
    assert int(stats["rows"]) == int(rows) == t * k
    reg = MetricsRegistry()
    observe_moe(reg, {"moe_rows": stats["rows"]})
    assert reg.value("moe.dropped") == 0 and reg.value("moe.rows") == t * k
    # the reference's planted capacity fault does drop
    dropped, _, fewer = ref.moe(dict(prog, capacity_factor=1.25), p, x,
                                ref.exact_dot)
    assert int(fewer) < t * k
    assert np.abs(np.asarray(dropped - want)).max() > 1e-2


def test_driver_finds_the_reference_the_configuration_names():
    from bench.reference import mamba2
    cell = spec.cell(WORKLOAD)
    assert cell.driver == "train_ref"
    assert train_ref.reference_module(cell.config) is ref
    assert train_ref.reference_module({"reference": "mamba2"}) is mamba2
    with pytest.raises(ModuleNotFoundError):
        train_ref.reference_module({"reference": "no_such_model"})


def _tiny_cell(**traffic_kw):
    cell = spec.cell(WORKLOAD)
    config = dict(cell.config, vocab_size=250, program=tiny_program(),
                  compute_dtype="float32")
    t = dict(cell.traffic, seq_len=128, batch_per_chip=2, batch_pool=4,
             **traffic_kw)
    return dataclasses.replace(cell, config=config, traffic=t)


def test_tiny_cell_runs_correct_and_counts_the_routed_rows():
    res = bench_run.run_cell(_tiny_cell(), SEED, 0.2, False,
                             time.perf_counter())
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"setup_s", "tokens_per_s"}


def test_tiny_cell_with_half_the_batch_is_not_correct(monkeypatch):
    from repro.models import transformer
    real = transformer.loss_and_aux

    def half(cfg, params, batch, **kw):
        n = batch["tokens"].shape[0] // 2
        return real(cfg, params, {k: v[:n] for k, v in batch.items()}, **kw)
    monkeypatch.setattr(transformer, "loss_and_aux", half)
    res = bench_run.run_cell(_tiny_cell(), SEED, 0.2, False,
                             time.perf_counter())
    assert not res["correct"], res["checks"]


def test_window_counts_the_moe_rows():
    cell = _tiny_cell()
    drv = spec.driver(cell.driver).Driver(cell, SEED)
    counts = drv.window(0.05)
    # 2 rows of 128 tokens, 3 choices each, 2 MoE layers; a quarter of
    # the choices or so land on the 4 held experts of 16
    assert 0 < counts["moe_rows"] <= counts["steps"] * 2 * 2 * 128 * 3
    assert counts["moe_dropped"] == 0
    drv.free()


def test_moe_flops_by_hand():
    p = {"d_model": 8, "n_heads": 2, "mla_qk_nope": 4, "mla_qk_rope": 2,
         "mla_v_dim": 4, "mla_kv_lora": 4, "n_experts": 4, "experts_held": 2,
         "experts_per_token": 2, "moe_d_ff": 3, "n_shared_experts": 1,
         "first_dense_layers": 1, "d_ff": 5, "n_layers": 2, "vocab": 10}
    # MLA: projections 2·8·(2·6 + 4 + 2) + 2·4·2·8 + 2·2·4·8 = 544;
    # scores over a mean context of 2: 2·2·6·2 + 2·2·4·2 = 80
    assert flops_moe.mla_per_token(p, 3) == 624
    # router 2·8·4, shared 6·8·3, routed 6·8·3 · (2 · 2/4)
    assert flops_moe.moe_ffn_per_token(p) == 64 + 144 + 144
    # dense layer (MLA + 6·8·5), MoE layer, head 2·8·10
    assert flops_moe.forward_per_token(p, 3) == (624 + 240) + (624 + 352) + 160
    assert flops_moe.train_per_token(p, 3) == 6000
    assert flops_moe.experts_flops(p, 10) == 18 * 8 * 3 * 10
    # 2 steps: 3 passes · 2 bytes · (2·1·2·3·8·3 weights + 10·2·8 rows)
    assert flops_moe.experts_bytes(p, 10, 2) == 3 * 2 * (288 + 160)


def test_cell_flops_per_token():
    """The cell's program: 6 layers of width 2048, 8192 positions."""
    p = spec.cell(WORKLOAD).config["program"]
    mla = flops_moe.mla_per_token(p, 8192)
    assert mla == pytest.approx(2 * 13_762_560 + 2 * 16 * 320 * 4096.5)
    assert flops_moe.moe_ffn_per_token(p) == pytest.approx(
        2 * 2048 * 64 + 6 * 2048 * 2816 + 6 * 2048 * 1408 * 0.75)
    assert 2.4e9 < flops_moe.train_per_token(p, 8192) < 2.8e9


def _made_up() -> tuple[Trace, scopes.OpScopes]:
    # one chip, window 0..1000 ns: MLA 100 ns, routing 30 + 20 + 10,
    # the grouped products 200, the shared experts 40, unscoped 50
    ops = [("fusion.1", 0, 100), ("sort.2", 100, 30), ("gather.3", 130, 20),
           ("fusion.4", 150, 10), ("custom-call.5", 160, 200),
           ("fusion.6", 360, 40), ("copy.7", 400, 50)]
    tr = Trace({"/device:TPU:0": trace.nest(ops)},
               [Span("bench.window", 0, 1000)], 0, 1000)
    root = "jit(step_body)/jvp()/while/body/closed_call/"
    s = scopes.OpScopes("jit_step_body", {
        "fusion.1": root + "lm.mla/dot_general",
        "sort.2": root + "lm.moe.route/sort",
        "gather.3": root + "transpose(jvp(lm.moe.dispatch))/gather",
        "fusion.4": root + "checkpoint/lm.moe.combine/dot_general",
        "custom-call.5": root + "lm.moe.experts/pallas_call",
        "fusion.6": root + "lm.moe.shared/dot_general",
        "copy.7": "jit(step_body)/copy",
    })
    return tr, s


@pytest.mark.parametrize("metric,ns", [
    ("train.mla_ms_per_step", 100), ("train.moe_route_ms_per_step", 60),
    ("train.moe_experts_ms_per_step", 200)])
def test_moe_scope_readers_on_a_made_up_trace(metric, ns):
    tr, s = _made_up()
    ctx = {"trace": tr, "op_scopes": s, "counts": {"steps": 2}}
    assert spec.reader(metric).read(ctx) == pytest.approx(1e3 * ns * 1e-9 / 2)
    bare = scopes.OpScopes("jit_f", {k: "jit(f)/mul" for k in s.paths})
    assert spec.reader(metric).read(dict(ctx, op_scopes=bare)) is None
    unscoped = spec.reader("device.unscoped_share.train").read(ctx)
    assert unscoped == pytest.approx(100 * 50 / 450)


def test_experts_roofline_and_moe_mfu_by_hand():
    tr, s = _made_up()
    cell = spec.cell(WORKLOAD)
    p = cell.config["program"]
    peaks = spec.peaks("TPU v5 lite")
    counts = {"steps": 2, "moe_rows": 5000, "tokens": 32768,
              "window_s": 1e-6}
    ctx = {"trace": tr, "op_scopes": s, "counts": counts, "cell": cell,
           "chips": 1, "peaks": peaks}
    flops = 18 * 2048 * 1408 * 5000
    nbytes = 3 * 2 * (2 * 5 * 8 * 3 * 2048 * 1408 + 5000 * 2 * 2048)
    least = max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])
    assert spec.reader("moe.experts_roofline").read(ctx) == pytest.approx(
        100 * least / 200e-9)
    # no routed rows counted, or no scoped program: nothing to read
    assert spec.reader("moe.experts_roofline").read(
        dict(ctx, counts=dict(counts, moe_rows=0))) is None
    assert spec.reader("moe.experts_roofline").read(
        {"trace": None, "counts": counts}) is None
    mfu = spec.reader("train.moe_mfu").read(ctx)
    assert mfu == pytest.approx(100 * flops_moe.train_per_token(p, 8192)
                                * 32768 / (1e-6 * peaks["bf16_flops"]))
