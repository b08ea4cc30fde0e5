"""HLO analyzer, data pipeline, serving engine, analytic FLOPs."""
import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.data import pipeline
from repro.launch import analytic, hlo_analysis
from repro.models import get_model
from repro.serve.engine import BatchedServer


def test_hlo_while_trip_counting():
    """A 6-iteration scanned matmul must report 6× one body's FLOPs."""
    def f(x, ws):
        def body(x, w):
            return jnp.tanh(x @ w), None
        x, _ = jax.lax.scan(body, x, ws)
        return x.sum()
    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    ws = jax.ShapeDtypeStruct((6, 256, 256), jnp.float32)
    txt = jax.jit(f).lower(x, ws).compile().as_text()
    st = hlo_analysis.analyze(txt)
    assert st.flops == 6 * 2 * 128 * 256 * 256
    assert 6 in st.while_trips.values()


def test_hlo_nested_while():
    def f(x, ws):
        def outer(x, wgroup):
            def inner(x, w):
                return x @ w, None
            x, _ = jax.lax.scan(inner, x, wgroup)
            return x, None
        x, _ = jax.lax.scan(outer, x, ws)
        return x.sum()
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((3, 4, 64, 64), jnp.float32)
    txt = jax.jit(f).lower(x, ws).compile().as_text()
    st = hlo_analysis.analyze(txt)
    assert st.flops == 12 * 2 * 64 * 64 * 64      # 3 × 4 iterations


def test_hlo_dus_in_place():
    """Cache updates must count the update slice, not the whole cache
    (donated input → true in-place update)."""
    def f(cache, tok):
        return jax.lax.dynamic_update_slice_in_dim(cache, tok, 5, 0)
    cache = jax.ShapeDtypeStruct((1024, 128), jnp.float32)
    tok = jax.ShapeDtypeStruct((1, 128), jnp.float32)
    txt = jax.jit(f, donate_argnums=(0,)).lower(cache, tok).compile() \
        .as_text()
    st = hlo_analysis.analyze(txt)
    assert st.bytes_written <= 4 * 128 * 4   # update slice, small slack


def test_roofline_terms():
    t = hlo_analysis.roofline_terms(197e12, 0.0, 0.0, 256)
    assert abs(t["compute_s"] - 1.0) < 1e-9
    assert t["dominant"] == "compute"


def test_analytic_flops_scaling():
    cfg = configs.load("tinyllama_1_1b").CONFIG
    m = get_model(cfg)
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    train = analytic.model_flops(cfg, shapes, configs.TRAIN_4K)
    prefill = analytic.model_flops(cfg, shapes, configs.PREFILL_32K)
    decode = analytic.model_flops(cfg, shapes, configs.DECODE_32K)
    assert train > prefill > decode
    n = analytic.active_params(cfg, shapes)
    assert 0.9e9 < n < 1.15e9
    # train ≈ 6·N·D(tokens) within the attention-term margin
    d = 256 * 4096
    assert 1.0 <= train / (6 * n * d) < 1.4


def test_moe_active_params():
    cfg = configs.load("qwen3_moe_235b_a22b").CONFIG
    m = get_model(cfg)
    shapes = jax.eval_shape(m.init, jax.random.PRNGKey(0))
    n = analytic.active_params(cfg, shapes)
    assert 15e9 < n < 30e9     # "a22b" ≈ 22B active


def test_pipeline_determinism_and_shapes():
    cfg = configs.load("tinyllama_1_1b").SMOKE
    a = next(pipeline.synthetic_batches(cfg, 4, 32, seed=7, prefetch=False))
    b = next(pipeline.synthetic_batches(cfg, 4, 32, seed=7, prefetch=False))
    assert np.array_equal(a["tokens"], b["tokens"])
    assert a["tokens"].shape == (4, 32)
    assert (a["tokens"] >= 0).all() and (a["tokens"] < cfg.vocab).all()
    # labels are next-token shifted
    assert a["labels"].shape == (4, 32)


def test_batch_structs_per_kind():
    cfg = configs.load("whisper_medium").CONFIG
    t = pipeline.batch_structs(cfg, configs.TRAIN_4K)
    assert t["tokens"].shape == (256, 4096)
    assert t["enc_frames"].shape == (256, 1500, 1024)
    d = pipeline.batch_structs(cfg, configs.DECODE_32K)
    assert d["tokens"].shape == (128, 1)
    assert "enc_frames" not in d


def test_batched_server_end_to_end():
    cfg = configs.load("tinyllama_1_1b").SMOKE.scaled(dtype=jnp.float32)
    m = get_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    srv = BatchedServer(m, params, slots=4, max_len=32)
    rng = np.random.default_rng(0)
    reqs = [srv.submit(rng.integers(0, cfg.vocab, size=3), max_new=5)
            for _ in range(6)]
    srv.run(max_steps=500)
    for r in reqs:
        assert r.done and len(r.out) >= 1
        assert all(0 <= t < cfg.vocab for t in r.out)


_CACHE_PROBE = """
import jax, jax.numpy as jnp
from repro.launch.cache import enable_compile_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
print(enable_compile_cache())
for scope in ("a.first", "b.second"):     # the same ops, scoped apart
    def f(x, scope=scope):
        with jax.named_scope(scope):
            return x * 2 + 1
    jax.jit(f)(jnp.ones(8)).block_until_ready()
"""


def _cache_probe(env_dir):
    """Run the cache helper in a child (the setting is process-wide) and
    return the directory it reports."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    r = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.strip().splitlines()[-1]


def test_compile_cache_follows_env(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the helper sets nothing and the
    compiled program lands there."""
    assert _cache_probe(tmp_path) == str(tmp_path)
    assert any(tmp_path.iterdir()), "no cache entry written"


def test_compile_cache_keys_hold_the_scopes(tmp_path):
    """Two modules that differ only in their ``named_scope`` are two
    entries: a profile reads an op's scope from the executable."""
    _cache_probe(tmp_path)
    entries = [p for p in tmp_path.iterdir()
               if p.name.startswith("jit_f-")
               and not p.name.endswith("-atime")]
    assert len(entries) == 2, entries


def test_compile_cache_default_is_checkout_path():
    """Without the variable the cache is the fixed <checkout>/.jax_cache."""
    import pathlib
    from repro.launch import cache
    root = pathlib.Path(__file__).resolve().parents[1]
    assert cache.DEFAULT_DIR == root / ".jax_cache"
    assert _cache_probe(None) == str(root / ".jax_cache")
