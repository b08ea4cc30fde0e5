"""Transport-layer unit/property tests (single device).

The multi-device wire schedules live in ``multidevice_checks.py``
(group ``transports``); here: the batched (leading-bucket-axis) forms of
the sparse merge and int8 quantizer, k derivation from unpadded extents,
the dispatch table, and the arena plan's valid-extent metadata.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import arena, compression, sparse, transports
from repro.core.engine import FlareConfig, GradReducer
from repro.core.sparse import SENTINEL, merge_coordinate_lists, sparse_k


def _random_lists(rng, b, n, size):
    """(B, n) index-sorted, index-unique, sentinel-padded lists."""
    idx = np.full((b, n), SENTINEL, np.int32)
    val = np.zeros((b, n), np.float32)
    for i in range(b):
        u = np.unique(rng.integers(0, size, rng.integers(0, n + 1)))
        idx[i, :len(u)] = u
        val[i, :len(u)] = rng.normal(size=len(u))
    return idx, val


@given(st.integers(0, 200))
@settings(max_examples=25, deadline=None)
def test_merge_batched_properties(seed):
    """Leading-axis merge preserves, per bucket: index-sortedness,
    uniqueness of valid indices, and the sum of values at every index."""
    rng = np.random.default_rng(seed)
    b, n, size = int(rng.integers(1, 6)), 8, 64
    ia, va = _random_lists(rng, b, n, size)
    ib, vb = _random_lists(rng, b, n, size)
    mi, mv = merge_coordinate_lists(jnp.asarray(ia), jnp.asarray(va),
                                    jnp.asarray(ib), jnp.asarray(vb))
    assert mi.shape == mv.shape == (b, 2 * n)
    mi, mv = np.asarray(mi), np.asarray(mv)
    for i in range(b):
        assert (np.diff(mi[i].astype(np.int64)) >= 0).all(), "sorted"
        valid = mi[i][mi[i] < size]
        assert len(np.unique(valid)) == len(valid), "unique"
        dense = np.zeros(size, np.float32)
        dense[ia[i][ia[i] < size]] += va[i][ia[i] < size]
        dense[ib[i][ib[i] < size]] += vb[i][ib[i] < size]
        got = np.zeros(size, np.float32)
        np.add.at(got, mi[i][mi[i] < size], mv[i][mi[i] < size])
        np.testing.assert_allclose(got, dense, atol=1e-5)


@given(st.integers(0, 200))
@settings(max_examples=25, deadline=None)
def test_merge_batched_equals_per_bucket(seed):
    """The (B, n) form is exactly B independent 1-d merges (bitwise)."""
    rng = np.random.default_rng(seed)
    b, n, size = int(rng.integers(2, 5)), 8, 64
    ia, va = _random_lists(rng, b, n, size)
    ib, vb = _random_lists(rng, b, n, size)
    mi, mv = merge_coordinate_lists(jnp.asarray(ia), jnp.asarray(va),
                                    jnp.asarray(ib), jnp.asarray(vb))
    for i in range(b):
        ri, rv = merge_coordinate_lists(
            jnp.asarray(ia[i]), jnp.asarray(va[i]),
            jnp.asarray(ib[i]), jnp.asarray(vb[i]))
        assert np.asarray(mi[i]).tobytes() == np.asarray(ri).tobytes()
        assert np.asarray(mv[i]).tobytes() == np.asarray(rv).tobytes()


def test_topk_masked_k_eff():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=100).astype(np.float32))
    # full k_eff == unmasked path, bitwise
    v0, i0 = sparse.topk_sparsify(x, 10)
    v1, i1 = sparse.topk_sparsify(x, 10, 10)
    assert np.asarray(v0).tobytes() == np.asarray(v1).tobytes()
    assert np.asarray(i0).tobytes() == np.asarray(i1).tobytes()
    # masked: exactly k_eff valid entries = the k_eff largest magnitudes
    v2, i2 = sparse.topk_sparsify(x, 10, 4)
    i2, v2 = np.asarray(i2), np.asarray(v2)
    valid = i2 < 100
    assert valid.sum() == 4
    assert (v2[~valid] == 0).all() and (i2[~valid] == SENTINEL).all()
    top4 = set(np.argsort(-np.abs(np.asarray(x)))[:4].tolist())
    assert set(i2[valid].tolist()) == top4
    assert (np.diff(i2[valid]) > 0).all()


def test_sparse_k_single_source_of_truth():
    """Satellite: both engine paths derive k identically, clamped to the
    unpadded extent — frac >= 1 must not crash and padded sizes must not
    inflate k."""
    assert sparse_k(1.0, 100) == 100
    assert sparse_k(1.5, 100) == 100       # k > size would fail top-k
    assert sparse_k(1e-6, 100) == 1
    assert sparse_k(0.25, 100) == 25
    assert sparse_k(0.5, 1) == 1


def test_arena_valid_extents():
    leaves = [jnp.zeros((s,), jnp.float32) for s in (1000, 3, 500)]
    plan = arena.build_plan(leaves, bucket_bytes=2048, pad_multiple=16)
    (g,) = plan.groups
    ext = g.valid_extents
    assert len(ext) == g.num_buckets
    assert sum(ext) == g.used_elems == 1503
    assert all(0 < e <= g.bucket_elems for e in ext)
    # padding is tail-only: every bucket but the last is full
    assert all(e == g.bucket_elems for e in ext[:-1])
    # and transport k derives from these, not the padded total
    ks = [sparse_k(0.1, e) for e in ext]
    assert ks[-1] <= ks[0]


def test_quantize_batched_matches_flat():
    rng = np.random.default_rng(0)
    xb = rng.normal(size=(5, 1024)).astype(np.float32) * 37
    q, s = compression.quantize_int8(jnp.asarray(xb))
    assert q.shape == (5, 1024) and s.shape == (5, 4)
    for i in range(5):
        qf, sf = compression.quantize_int8(jnp.asarray(xb[i]))
        assert np.asarray(q[i]).tobytes() == np.asarray(qf).tobytes()
        assert np.asarray(s[i]).tobytes() == np.asarray(sf).tobytes()
    deq = compression.dequantize_int8(q, s)
    assert deq.shape == (5, 1024)
    np.testing.assert_allclose(np.asarray(deq), xb,
                               atol=np.abs(xb).max() / 127 * 1.01)
    # roundtrip pads/unpads ragged last axes, batched
    rt = compression.quantize_roundtrip(jnp.asarray(xb[:, :1000]))
    assert rt.shape == (5, 1000)
    rt1 = compression.quantize_roundtrip(jnp.asarray(xb[0, :1000]))
    assert np.asarray(rt[0]).tobytes() == np.asarray(rt1).tobytes()


def test_dispatch_table():
    """from_config: lossy transports for floats only, dense otherwise."""
    dense = FlareConfig()
    sp = FlareConfig(sparse_k_frac=0.01)
    q8 = FlareConfig(compression="int8")
    table = [
        (dense, jnp.float32, transports.DenseTransport),
        (sp, jnp.float32, transports.SparseTransport),
        (sp, jnp.int32, transports.DenseTransport),
        (q8, jnp.float32, transports.Int8Transport),
        (q8, jnp.int32, transports.DenseTransport),
    ]
    for cfg, dt, cls in table:
        t = transports.from_config(cfg, dt)
        assert type(t) is cls, (cfg, dt)
        assert t.axes == tuple(cfg.axes)
    # sparse wins over int8 when both are configured
    both = FlareConfig(sparse_k_frac=0.01, compression="int8")
    assert isinstance(transports.from_config(both, jnp.float32),
                      transports.SparseTransport)
    assert transports.from_config(dense, jnp.float32).needs_state is False
    assert transports.from_config(sp, jnp.float32).needs_state is True


def test_construction_without_mesh_defers_validation():
    # no ambient mesh → precondition check defers to trace time
    r = GradReducer(FlareConfig(axes=("nonexistent",), sparse_k_frac=0.5))
    assert r.needs_state


def test_hierarchical_config_threading():
    """FlareConfig.hierarchical reaches every transport class; the wire
    schedules themselves are exercised in multidevice group `hierarchy`."""
    for kw in [dict(), dict(sparse_k_frac=0.01), dict(compression="int8")]:
        cfg = FlareConfig(axes=("pod", "data"), hierarchical=True, **kw)
        assert transports.from_config(cfg, jnp.float32).hierarchical is True
        cfg = FlareConfig(axes=("pod", "data"), **kw)
        assert transports.from_config(cfg, jnp.float32).hierarchical is None
    # a single-axis mesh has a one-level tree: forcing hierarchical is a
    # config error, and a 1-axis transport never picks it on its own
    with pytest.raises(ValueError):
        FlareConfig(axes=("data",), hierarchical=True)
    t = transports.DenseTransport(("data",), hierarchical=True)
    assert t._use_hierarchy() is False
    # the force flag and an explicit dense algorithm must agree
    with pytest.raises(ValueError):
        FlareConfig(axes=("pod", "data"), algorithm="ring",
                    hierarchical=True)
    with pytest.raises(ValueError):
        FlareConfig(axes=("pod", "data"), algorithm="hierarchical",
                    hierarchical=False)
    FlareConfig(axes=("pod", "data"), algorithm="hierarchical")   # fine
    FlareConfig(axes=("pod", "data"), algorithm="ring",
                hierarchical=False)                               # fine


def test_engine_pad_multiple_covers_quant_blocks():
    """With int8 transport the plan pad multiple makes every bucket chunk
    a whole number of quantization blocks (no runtime pad on the wire);
    a group the int8 config leaves on the dense wire (an integer dtype)
    pads as the dense schedule does."""
    q8 = FlareConfig(compression="int8")
    for world in (1, 2, 8):
        for t in (transports.from_config(q8, jnp.float32),
                  transports.from_config(
                      FlareConfig(compression="int8", transport="innetwork"),
                      jnp.float32)):
            pad = t.pad_multiple(world, 1000, 4)
            assert pad % (world * transports.QUANT_BLOCK) == 0
            assert pad % (2 * world) == 0
    assert transports.from_config(q8, jnp.int32).pad_multiple(8, 1000, 4) \
        == 16
    dense = transports.from_config(FlareConfig(), jnp.float32)
    assert dense.pad_multiple(8, 1000, 4) == 16
