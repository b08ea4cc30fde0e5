"""Single-device engine machinery: config validation, policy, sparse merge."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import collectives as coll
from repro.core.engine import FlareConfig
from repro.core.sparse import (densify_step, expected_sparse_wire_bytes,
                               merge_coordinate_lists, topk_sparsify,
                               SENTINEL)
from repro.core.reproducible import combine_order


def test_flare_config_validation():
    with pytest.raises(ValueError):
        FlareConfig(reproducible=True, compression="int8")
    with pytest.raises(ValueError):
        FlareConfig(reproducible=True, sparse_k_frac=0.01)
    with pytest.raises(ValueError):
        FlareConfig(compression="int4")


def test_select_algorithm_matches_paper():
    assert coll.select_algorithm(64 << 10) == "fixed_tree"
    assert coll.select_algorithm(256 << 10) == "rhd"
    assert coll.select_algorithm(1 << 20) == "ring"
    assert coll.select_algorithm(1 << 20, multi_level=True) == "two_level"
    assert coll.select_algorithm(1 << 20, reproducible=True) == "fixed_tree"


@given(st.integers(2, 9))
@settings(max_examples=8, deadline=None)
def test_combine_order_is_complete_tree(logp):
    p = 1 << logp
    order = combine_order(p)
    assert len(order) == p - 1          # a reduction tree has P−1 combines


def test_wire_bytes_accounting():
    z = 1 << 20
    ring = coll.wire_bytes_per_rank(z, 16, algorithm="ring")
    tree = coll.wire_bytes_per_rank(z, 16, algorithm="fixed_tree")
    two = coll.wire_bytes_per_rank(z, 16, 2, algorithm="two_level")
    assert abs(ring - 2 * z * 15 / 16) < 1
    assert abs(tree - 4 * z) < 1        # log2(16) = 4
    assert two < ring * 1.1             # the paper's traffic reduction


# ---------------------------------------------------------------------------
# sparse merge machinery (single-device parts of §7)
# ---------------------------------------------------------------------------

@given(st.integers(0, 100))
@settings(max_examples=20, deadline=None)
def test_merge_coordinate_lists(seed):
    rng = np.random.default_rng(seed)
    size = 64
    ia = np.unique(rng.integers(0, size, 8)).astype(np.int32)
    ib = np.unique(rng.integers(0, size, 8)).astype(np.int32)
    va = rng.normal(size=len(ia)).astype(np.float32)
    vb = rng.normal(size=len(ib)).astype(np.float32)
    pad = lambda i, v, n: (
        np.concatenate([i, np.full(n - len(i), SENTINEL, np.int32)]),
        np.concatenate([v, np.zeros(n - len(v), np.float32)]))
    ia_p, va_p = pad(ia, va, 8)
    ib_p, vb_p = pad(ib, vb, 8)
    mi, mv = merge_coordinate_lists(jnp.asarray(ia_p), jnp.asarray(va_p),
                                    jnp.asarray(ib_p), jnp.asarray(vb_p))
    dense = np.zeros(size, np.float32)
    dense[ia] += va
    dense[ib] += vb
    got = np.zeros(size, np.float32)
    for i, v in zip(np.asarray(mi), np.asarray(mv)):
        if i < size:
            got[i] += v
    np.testing.assert_allclose(got, dense, atol=1e-5)
    # unique indices in output
    valid = np.asarray(mi)[np.asarray(mi) < size]
    assert len(np.unique(valid)) == len(valid)


def test_topk_sparsify_sorted_unique():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=100).astype(np.float32))
    v, i = topk_sparsify(x, 10)
    ii = np.asarray(i)
    assert (np.diff(ii) > 0).all()
    np.testing.assert_allclose(np.asarray(v), np.asarray(x)[ii])


def test_densify_schedule_static():
    assert densify_step(1000, 1000, 0.25)
    assert not densify_step(100, 1000, 0.25)
    # wire bytes shrink when density threshold forces early densify only
    # for large k
    lo = expected_sparse_wire_bytes(1 << 20, 1 << 10, 256)
    hi = expected_sparse_wire_bytes(1 << 20, 1 << 16, 256)
    assert hi > lo
