"""Per-architecture smoke + consistency tests (reduced configs, 1 CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import get_model

ARCHS = configs.ARCHS


def _batch(cfg, key, b, s):
    batch = {"tokens": jax.random.randint(key, (b, s), 0, cfg.vocab),
             "labels": jax.random.randint(key, (b, s), 0, cfg.vocab)}
    if cfg.family == "audio":
        batch["enc_frames"] = jax.random.normal(
            key, (b, cfg.encoder_tokens, cfg.d_model)) * 0.1
    if cfg.family == "vlm":
        batch["vision_embeds"] = jax.random.normal(
            key, (b, cfg.vision_tokens, cfg.d_model)) * 0.1
    return batch


@pytest.fixture(scope="module", params=ARCHS)
def model_env(request):
    cfg = configs.load(request.param).SMOKE.scaled(dtype=jnp.float32)
    m = get_model(cfg)
    key = jax.random.PRNGKey(0)
    return request.param, cfg, m, m.init(key), key


def test_train_step_shapes_finite(model_env):
    arch, cfg, m, params, key = model_env
    batch = _batch(cfg, key, 2, 16)
    loss = jax.jit(m.loss)(params, batch)
    assert loss.shape == ()
    assert np.isfinite(float(loss)), f"{arch}: non-finite loss"
    grads = jax.grad(lambda p: m.loss(p, batch))(params)
    for leaf in jax.tree.leaves(grads):
        assert np.isfinite(np.asarray(leaf)).all(), f"{arch}: NaN grads"


def test_prefill_decode_shapes(model_env):
    arch, cfg, m, params, key = model_env
    b, s = 2, 16
    batch = _batch(cfg, key, b, s)
    logits, cache = jax.jit(m.prefill)(params, batch)
    assert logits.shape == (b, 1, cfg.vocab)
    assert np.isfinite(np.asarray(logits)).all()
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    logits2, cache2 = jax.jit(m.decode)(params, tok, cache)
    assert logits2.shape == (b, 1, cfg.vocab)
    assert np.isfinite(np.asarray(logits2)).all()
    assert int(cache2["pos"]) == int(cache["pos"]) + 1


def test_zero_cache_decode(model_env):
    """The dry-run decode path: one token against a pre-allocated cache."""
    arch, cfg, m, params, key = model_env
    b, s = 2, 16
    cache = m.init_cache(b, s)
    tok = jax.random.randint(key, (b, 1), 0, cfg.vocab)
    logits, _ = jax.jit(m.decode)(params, tok, cache)
    assert np.isfinite(np.asarray(logits)).all(), arch


def test_prefill_vs_decode_consistency(model_env):
    """decode(prefill(t[:-1]), t[-1]) ≡ prefill(t) last logits."""
    arch, cfg, m, params, key = model_env
    b, s = 2, 16
    batch = _batch(cfg, key, b, s)
    b1 = dict(batch)
    b1["tokens"] = batch["tokens"][:, :-1]
    _, cache = jax.jit(m.prefill)(params, b1)

    def grow(a):
        if hasattr(a, "ndim") and a.ndim >= 3 and a.shape[2] == s - 1:
            pad = jnp.zeros(a.shape[:2] + (1,) + a.shape[3:], a.dtype)
            return jnp.concatenate([a, pad], axis=2)
        return a
    cache = jax.tree.map(grow, cache)
    logits_d, _ = jax.jit(m.decode)(params, batch["tokens"][:, -1:], cache)
    logits_p, _ = jax.jit(m.prefill)(params, batch)
    rel = np.abs(np.asarray(logits_p) - np.asarray(logits_d)).max() \
        / (np.abs(np.asarray(logits_p)).max() + 1e-9)
    assert rel < 2e-3, f"{arch}: prefill/decode divergence {rel:.2e}"


def test_training_reduces_loss(model_env):
    arch, cfg, m, params, key = model_env
    batch = _batch(cfg, key, 4, 16)

    @jax.jit
    def step(p):
        l, g = jax.value_and_grad(lambda q: m.loss(q, batch))(p)
        return l, jax.tree.map(lambda a, b: a - 0.05 * b, p, g)
    losses = []
    p = params
    for _ in range(5):
        l, p = step(p)
        losses.append(float(l))
    assert losses[-1] < losses[0], f"{arch}: loss not decreasing {losses}"


def test_full_config_param_counts():
    """Full configs must land on the published parameter counts."""
    expected = {
        "qwen3_moe_235b_a22b": (230e9, 240e9),
        "deepseek_v2_lite_16b": (14e9, 17e9),
        "mamba2_370m": (0.3e9, 0.5e9),
        "whisper_medium": (0.7e9, 0.85e9),
        "llama32_vision_90b": (80e9, 95e9),
        "gemma2_27b": (26e9, 29e9),
        "tinyllama_1_1b": (1.0e9, 1.2e9),
        "granite_20b": (19e9, 29e9),   # llama-arch spec per assignment
        "gemma2_2b": (2.2e9, 2.8e9),
        "zamba2_1_2b": (1.0e9, 1.4e9),
    }
    for arch, (lo, hi) in expected.items():
        cfg = configs.load(arch).CONFIG
        m = get_model(cfg)
        shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0))
        n = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
        assert lo <= n <= hi, f"{arch}: {n / 1e9:.2f}B not in [{lo}, {hi}]"


def test_mamba_chunked_equals_recurrent():
    cfg = configs.load("mamba2_370m").SMOKE.scaled(dtype=jnp.float32)
    m = get_model(cfg)
    key = jax.random.PRNGKey(1)
    params = m.init(key)
    toks = jax.random.randint(key, (2, 16), 0, cfg.vocab)
    logits_p, _ = jax.jit(m.prefill)(params, {"tokens": toks})
    cache = m.init_cache(2, 16)
    cache["pos"] = jnp.int32(0)
    for t in range(16):
        logits_d, cache = jax.jit(m.decode)(params, toks[:, t:t + 1], cache)
    rel = np.abs(np.asarray(logits_p[:, -1]) - np.asarray(logits_d[:, -1])
                 ).max() / np.abs(np.asarray(logits_p)).max()
    assert rel < 1e-3, f"SSD chunked vs recurrent: {rel:.2e}"


def _log_decay(q, seed=0):
    """(2, 3, 4, q) log-decays drawn as the SSD makes them: softplus(dt)
    times −exp(A_log), A_log up to log 16, so about −11 a position."""
    kd, ka = jax.random.split(jax.random.PRNGKey(seed))
    dt = jax.random.normal(kd, (2, 3, 4, q))
    a_log = jax.random.uniform(ka, (3, 1, 1), maxval=np.log(16.0))
    return jax.nn.softplus(dt) * -jnp.exp(a_log)


@pytest.mark.parametrize("q", [256, 64, 8])
def test_chunk_cumsum_equals_cumsum(q):
    from repro.models.mamba2 import _chunk_cumsum
    x = _log_decay(q)
    got, want = np.asarray(_chunk_cumsum(x)), np.asarray(jnp.cumsum(x, -1))
    assert got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-6 * np.abs(want).max())


@pytest.mark.parametrize("q", [256, 64, 8])
def test_chunk_cumsum_grad_equals_cumsum_grad(q):
    """The product's transpose is a reverse prefix sum, as cumsum's is."""
    from repro.models.mamba2 import _chunk_cumsum
    x = _log_decay(q)
    w = jax.random.normal(jax.random.PRNGKey(7), x.shape)
    got = np.asarray(jax.grad(lambda x: jnp.sum(w * _chunk_cumsum(x)))(x))
    want = np.asarray(jax.grad(lambda x: jnp.sum(w * jnp.cumsum(x, -1)))(x))
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-6 * np.abs(want).max())


@pytest.mark.parametrize("q", [256, 64, 8])
def test_segsum_contract(q):
    """out[i, j] = Σ_{k=j+1..i} x[k] on and below the diagonal, −inf
    above it."""
    from repro.models.mamba2 import _chunk_cumsum, segsum
    x = _log_decay(q)
    got = np.asarray(segsum(_chunk_cumsum(x)))
    assert got.shape == x.shape + (q,)
    cs = np.cumsum(np.asarray(x, np.float64), -1)
    want = cs[..., :, None] - cs[..., None, :]
    below = np.tril(np.ones((q, q), bool))
    assert np.all(got[..., ~below] == -np.inf)
    np.testing.assert_allclose(got[..., below], want[..., below], rtol=0,
                               atol=1e-6 * np.abs(cs).max())


def test_moe_router_balance_mechanism():
    """Capacity dropping must engage for adversarially unbalanced routing
    without corrupting kept tokens (positions are collision-free)."""
    cfg = configs.load("qwen3_moe_235b_a22b").SMOKE.scaled(
        dtype=jnp.float32, capacity_factor=0.5)
    m = get_model(cfg)
    key = jax.random.PRNGKey(0)
    params = m.init(key)
    batch = _batch(cfg, key, 2, 32)
    loss = jax.jit(m.loss)(params, batch)
    assert np.isfinite(float(loss))
