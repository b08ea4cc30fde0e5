"""The dropless MoE layer over the experts held, YaRN, and the train
step's MoE counter, on the CPU at small sizes."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import base, get_model

SMOKE = configs.load("deepseek_v2_lite_16b").SMOKE.scaled(dtype=jnp.float32)


def test_yarn_frequencies_and_mscale_written_out():
    """DeepSeek-V2-Lite's rope dimensions (64, theta 1e4) under YaRN
    factor 40 over 4096 positions, beta 32/1: the correction range is
    dimensions [10, 23]; below it the frequencies are kept, above it
    divided by 40, a linear ramp between."""
    assert (base.YARN_BETA_FAST, base.YARN_BETA_SLOW) == (32.0, 1.0)
    f = np.asarray(base.yarn_freqs(64, 1e4, 40.0, 4096))
    want = {0: 1.0, 10: 0.05623413251903491,     # 1e4 ** (-10/32), kept
            16: 0.0055,                          # 0.01 · (7/13 + 6/13/40)
            23: 3.33380358040831e-05,            # 1e4 ** (-23/32) / 40
            31: 3.3338035804083097e-06}          # 1e4 ** (-31/32) / 40
    for i, v in want.items():
        assert f[i] == pytest.approx(v, rel=1e-6), i
    assert f.shape == (32,) and np.all(np.diff(f) < 0)
    # mscale = 0.1 · 0.707 · ln 40 + 1; the softmax scale takes its square
    assert base.yarn_mscale(40.0, 0.707) == pytest.approx(1.2608037774058554)
    cfg = configs.load("deepseek_v2_lite_16b").CONFIG
    assert base.softmax_mscale(cfg) == pytest.approx(1.5896261651208736)
    np.testing.assert_allclose(np.asarray(base.rope_of(cfg, 64)), f)
    assert base.yarn_mscale(1.0, 0.707) == 1.0


def test_published_config_is_dropless_and_unnormalised():
    cfg = configs.load("deepseek_v2_lite_16b").CONFIG
    assert cfg.dropless and not cfg.norm_topk_prob
    assert (cfg.n_held, cfg.experts_per_token, cfg.aux_loss_alpha) == \
        (64, 6, 0.001)
    assert SMOKE.dropless and not SMOKE.norm_topk_prob
    assert SMOKE.yarn_factor == cfg.yarn_factor


def test_every_layer_stack_is_under_layers():
    shapes = jax.eval_shape(get_model(SMOKE.scaled(experts_held=3)).init,
                            jax.random.PRNGKey(0))
    assert set(shapes["layers"]) == {"dense", "moe"}
    assert shapes["layers"]["moe"]["ffn"]["w_gate"].shape == (2, 3, 64, 32)
    assert shapes["layers"]["moe"]["ffn"]["router"].shape == (2, 64, 8)
    assert shapes["layers"]["dense"]["attn"]["kv_norm"].shape == (1, 32)


@pytest.mark.parametrize("chunk", [0, 16], ids=["one-pass", "chunked"])
def test_grouped_product_follows_the_groups(chunk):
    """``moe_dropless`` against the same layer computed per token, one
    held expert at a time: the sorted grouped products give each
    routed row its own expert's output, rows of absent experts give
    nothing, whether the tokens pass at once or in chunks."""
    cfg = SMOKE.scaled(experts_held=5, moe_chunk=chunk)
    p = jax.tree.map(lambda a: a[0], get_model(cfg).init(
        jax.random.PRNGKey(3))["layers"]["moe"]["ffn"])
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        out, stats = base.moe_dropless(cfg, p, x)
        xt = x.reshape(-1, cfg.d_model)
        probs = jax.nn.softmax(xt @ p["router"], -1)
        gate, idx = jax.lax.top_k(probs, cfg.experts_per_token)
        want = base.swiglu(p["shared"], xt)
        for j in range(cfg.n_held):
            g = jnp.sum(jnp.where(idx == j, gate, 0.0), -1, keepdims=True)
            want = want + g * base.swiglu(
                {n: p[n][j] for n in ("w_gate", "w_up", "w_down")}, xt)
    np.testing.assert_allclose(np.asarray(out).reshape(want.shape),
                               np.asarray(want), rtol=2e-5, atol=2e-5)
    assert int(stats["rows"]) == int(jnp.sum(idx < cfg.n_held))


def test_megablox_grouped_product_equals_ragged_dot():
    """The TPU path (megablox ``gmm``, here interpreted) and the CPU path
    (``ragged_dot``) agree on uneven, empty and tile-straddling groups,
    on the rows past the last group (zeros), and in both gradients."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as mb
    x = jax.random.normal(jax.random.PRNGKey(0), (256, 128))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 128, 256))
    sizes = jnp.array([40, 0, 77, 30, 109], jnp.int32)

    def f_mb(x, w):
        return mb.gmm(x, w, sizes, jnp.float32, (128, 128, 128), None, None,
                      False, True)

    def f_rd(x, w):
        return jax.lax.ragged_dot(x, w, sizes[:-1],
                                  preferred_element_type=jnp.float32)
    a, b = f_mb(x, w), f_rd(x, w)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.asarray(a[147:]).any()
    sq = lambda f: jax.grad(lambda x, w: jnp.sum(f(x, w) ** 2),
                            argnums=(0, 1))(x, w)
    for u, v in zip(sq(f_mb), sq(f_rd)):
        np.testing.assert_allclose(np.asarray(u), np.asarray(v), rtol=1e-5,
                                   atol=1e-3)


def test_take_rows_transpose_is_a_gather():
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 3))
    idx = jnp.array([4, 0, 4, 1, 2, 3, 0, 1, 2, 3])          # each row twice
    # back[r·2 + j]: where row r's j-th copy sits in the output
    back = jnp.array([1, 6, 3, 7, 4, 8, 5, 9, 0, 2])
    g = jax.random.normal(jax.random.PRNGKey(1), (10, 3))
    got = jax.vjp(lambda a: base._take_rows(a, idx, back, 2), x)[1](g)[0]
    want = jax.vjp(lambda a: a[idx], x)[1](g)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_train_step_returns_moe_rows_and_mamba_none():
    """The step's metrics: loss, grad norm, and for the dropless MoE model
    the rows routed to the held experts, summed over layers and ranks."""
    from jax.sharding import AxisType

    from repro.core.engine import FlareConfig
    from repro.sharding import rules
    from repro.train import trainer

    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    mcfg = rules.MeshCfg(("data", "model"), (1, 1))
    tcfg = trainer.TrainConfig(flare=FlareConfig(axes=mcfg.reduce_axes))
    sds = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32) + 3,
             "labels": jnp.ones((2, 16), jnp.int32)}
    got = {}
    for name in ("deepseek_v2_lite_16b", "mamba2_370m"):
        model = get_model(configs.load(name).SMOKE.scaled(dtype=jnp.float32))
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        with jax.set_mesh(mesh):
            fn, psh, osh, _, init_opt = trainer.jit_train_step(
                model, mesh, mcfg, tcfg, shapes, {"tokens": sds,
                                                  "labels": sds})
            params = jax.jit(model.init, out_shardings=psh)(
                jax.random.PRNGKey(0))
            opt = jax.jit(init_opt, out_shardings=osh)(params)
            got[name] = fn(params, opt, batch)[2]
    ds = got["deepseek_v2_lite_16b"]
    assert set(ds) == {"loss", "grad_norm", "moe_rows"}
    # every token picks k of the 8 experts, all held: 2 MoE layers
    assert int(ds["moe_rows"]) == 2 * 32 * SMOKE.experts_per_token
    assert set(got["mamba2_370m"]) == {"loss", "grad_norm"}
    assert math.isfinite(float(ds["loss"]))


def test_observe_moe_registers_rows_and_no_drops():
    from repro.obs.metrics import MOE_COUNTERS, MetricsRegistry, observe_moe
    reg = MetricsRegistry()
    observe_moe(reg, {"moe_rows": np.int32(96)})
    observe_moe(reg, {"moe_rows": 32})
    assert reg.names("moe.") == sorted(MOE_COUNTERS)
    assert reg.value("moe.rows") == 128 and reg.value("moe.dropped") == 0


def test_capacity_path_is_unchanged_by_the_dropless_fields():
    """``capacity_factor`` > 0 keeps the capacity path (and its own
    renormalised gates) whatever the dropless fields say."""
    cfg = configs.load("qwen3_moe_235b_a22b").SMOKE.scaled(dtype=jnp.float32)
    assert not cfg.dropless
    p = jax.tree.map(lambda a: a[0], get_model(cfg).init(
        jax.random.PRNGKey(0))["layers"]["ffn"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model))
    a = base.moe_block(cfg, p, x)
    b = base.moe_block(dataclasses.replace(cfg, norm_topk_prob=False), p, x)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
