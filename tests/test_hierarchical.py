"""Differential multi-topology tests for the hierarchical schedules.

Two halves:

* **Parent-side** hypothesis property tests of the control plane — the
  ``ReductionTree`` ↔ mesh-axis mapping (``topology.mesh_levels``,
  ``build_mesh_tree``, ``transport_schedule``) and the analytic
  wire-byte model — which need no devices.

* **Child-side** hypothesis property tests of the data plane, executed
  under 8 fake CPU devices in a subprocess (the parent pytest process
  must keep 1 device; same pattern as ``multidevice_checks.py``):
  ``hierarchical_allreduce`` equals a flat ``psum`` within dtype
  tolerance for **every (pod, data) factorization of 8**, and the
  ``fixed_tree`` variant is **bitwise identical across permuted device
  orders** and across runs — the paper's F3 reproducibility claim for a
  multi-axis path.

Run a child check directly with::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python tests/test_hierarchical.py <check>
"""
import math
import os
import subprocess
import sys

if __name__ == "__main__":
    _N_DEV = 12 if (len(sys.argv) > 1
                    and sys.argv[1] == "sparse_nonpow2_fallback") else 8
    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={_N_DEV}")
    os.environ["JAX_PLATFORMS"] = "cpu"

try:                                                           # noqa: E402
    import hypothesis  # noqa: F401  (conftest installs the stub in pytest)
except ImportError:
    from repro import _hypothesis_stub
    _hypothesis_stub.install()

import pytest                                                  # noqa: E402
from hypothesis import given, settings, strategies as st       # noqa: E402

from repro.core import collectives as coll                     # noqa: E402
from repro.core import topology                                # noqa: E402

#: Every (pod, data) factorization of the 8 fake devices.
FACTORIZATIONS = [(1, 8), (2, 4), (4, 2), (8, 1)]


# ---------------------------------------------------------------------------
# Parent-side: control-plane properties (no devices needed).
# ---------------------------------------------------------------------------

@given(st.integers(1, 6), st.integers(1, 6), st.integers(1, 6))
@settings(max_examples=30, deadline=None)
def test_mesh_tree_matches_axes(a, b, c):
    """The nested tree's level fan-ins are exactly the non-trivial axis
    sizes, innermost first, and every host hangs off the tree."""
    sizes = (a, b, c)
    tree = topology.build_mesh_tree(sizes)
    assert tree.num_hosts == a * b * c
    nontrivial = [s for s in (c, b, a) if s > 1]   # innermost first
    assert list(tree.level_radices) == nontrivial
    assert len(tree.levels[-1]) == 1               # single root
    # level l holds prod(remaining outer axes) switches
    for lvl in range(1, len(tree.levels)):
        assert len(tree.levels[lvl]) == math.prod(nontrivial[lvl:])
    # levels bind to axes with fan-ins read off the tree
    levels = topology.mesh_levels(("a", "b", "c"), sizes)
    assert [l.fanin for l in levels] == nontrivial or a * b * c == 1


@given(st.sampled_from(FACTORIZATIONS))
@settings(max_examples=8, deadline=None)
def test_transport_schedule_policy(shape):
    """Hierarchical only when the leaf level actually aggregates
    (two real levels and fan-in > 2) — DESIGN.md §11."""
    pod, data = shape
    tree = topology.build_mesh_tree((pod, data))
    want = "hierarchical" if (pod > 1 and data > 2) else "flat"
    assert topology.transport_schedule(tree) == want


@given(st.integers(14, 24), st.sampled_from([(2, 4), (2, 8), (4, 16)]))
@settings(max_examples=20, deadline=None)
def test_hierarchical_wire_model(logz, shape):
    """The tree-driven schedule's inter-pod saving: hierarchical wire
    bytes stay below the flat per-axis ring whenever the leaf fan-in
    beats 2, and the inter-pod hop shrinks by exactly the fan-in."""
    p_out, p_in = shape
    z = 1 << logz
    hier = coll.wire_bytes_per_rank(z, p_in, p_out, algorithm="hierarchical")
    flat = coll.wire_bytes_per_rank(z, p_in, p_out, algorithm="ring")
    assert hier < flat
    # the hop across pods carries Z/fanin, not Z
    inter = hier - coll.wire_bytes_per_rank(z, p_in, 1, algorithm="ring")
    full_ring_outer = 2 * z * (p_out - 1) / p_out
    assert inter <= full_ring_outer / p_in + 1


def test_tree_drives_schedule_shapes():
    """mesh_levels is consistent with mesh_axes_as_tree for the shapes
    the data plane runs (sanity pin, not property-based)."""
    levels = topology.mesh_levels(("pod", "data"), (2, 4))
    assert [(l.axis, l.fanin) for l in levels] == [("data", 4), ("pod", 2)]
    levels = topology.mesh_levels(("pod", "data"), (1, 8))
    assert [(l.axis, l.fanin) for l in levels] == [("data", 8)]


# ---------------------------------------------------------------------------
# Child-side: data-plane properties (8 fake devices, run in a subprocess).
# ---------------------------------------------------------------------------

def _child_setup():
    import jax  # noqa: F401
    assert len(__import__("jax").devices()) >= 8, \
        "child needs XLA_FLAGS=--xla_force_host_platform_device_count=8"


def _run_on_mesh(mesh, fn, xs):
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    g = jax.jit(jax.shard_map(fn, in_specs=(P(("pod", "data"), None),),
                              out_specs=P(None),
                              axis_names={"pod", "data"}, check_vma=False))
    with jax.set_mesh(mesh):
        x = jax.device_put(xs, NamedSharding(mesh, P(("pod", "data"), None)))
        return np.asarray(g(x))


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=3, deadline=None)
def check_hier_matches_flat_psum(seed):
    """hierarchical_allreduce == flat psum within dtype tolerance, for
    every (pod, data) factorization of 8 fake devices (ragged Z too)."""
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from repro.launch import mesh as launch_mesh

    rng = np.random.default_rng(seed)
    z = int(rng.integers(5, 300))          # ragged lengths exercise padding
    xs = jnp.asarray((rng.normal(size=(8, z)) * 10).astype(np.float32))
    scale = np.abs(np.asarray(xs)).max()
    for pod, data in FACTORIZATIONS:
        mesh = launch_mesh.make_fake_mesh((pod, data))
        flat = _run_on_mesh(
            mesh, lambda x: lax.psum(x[0], ("pod", "data")), xs)
        for fixed in (False, True):
            got = _run_on_mesh(
                mesh, lambda x, f=fixed: coll.hierarchical_allreduce(
                    x[0], ("pod", "data"), fixed_tree=f), xs)
            assert np.allclose(got, flat, rtol=1e-5, atol=1e-4 * scale), (
                f"shape=({pod},{data}) fixed={fixed} Z={z}: "
                f"{np.abs(got - flat).max()}")


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=3, deadline=None)
def check_fixed_tree_bitwise_device_permutation(seed):
    """F3 for the multi-axis path: the fixed-tree hierarchical result is
    bitwise identical across permuted device orders (re-allocations of
    the same logical mesh) and across runs.  The ring variant is held to
    the numeric tolerance only — its combine order is also rank-pure,
    but the claim under test is the paper's fixed-tree one."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    rng = np.random.default_rng(seed)
    z = int(rng.integers(16, 257))
    xs = jnp.asarray((rng.normal(size=(8, z)) * 1e3).astype(np.float32))
    perm = rng.permutation(8)
    for pod, data in FACTORIZATIONS:
        fn = lambda x: coll.hierarchical_allreduce(
            x[0], ("pod", "data"), fixed_tree=True)
        # raw Mesh, not make_mesh: the device order must be EXACTLY the
        # permutation under test (make_mesh may normalize placement)
        mesh_a = Mesh(np.asarray(jax.devices()[:8]).reshape(pod, data),
                      ("pod", "data"))
        mesh_b = Mesh(np.asarray([jax.devices()[i]
                                  for i in perm]).reshape(pod, data),
                      ("pod", "data"))
        out_a = _run_on_mesh(mesh_a, fn, xs)
        out_b = _run_on_mesh(mesh_b, fn, xs)
        assert out_a.tobytes() == out_b.tobytes(), \
            f"device permutation changed bits: shape=({pod},{data})"
        again = _run_on_mesh(mesh_a, fn, xs)
        assert out_a.tobytes() == again.tobytes(), \
            f"rerun changed bits: shape=({pod},{data})"


def check_sparse_nonpow2_outer_fallback():
    """Regression: a (3, 4) mesh's tree prefers the hierarchical schedule
    (leaf fan-in 4), but the sparse merge cannot cross a non-power-of-two
    pod axis — auto mode must quietly keep the dense-across-pods
    two_level schedule (the pre-hierarchy behavior, correct for any
    outer size), while forcing ``hierarchical=True`` raises."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import transports
    from repro.core.engine import FlareConfig

    mesh = Mesh(np.asarray(jax.devices()[:12]).reshape(3, 4),
                ("pod", "data"))
    b, s = 2, 64
    rng = np.random.default_rng(7)
    xs = jnp.asarray(rng.normal(size=(12, b * s)).astype(np.float32))
    expect = np.asarray(xs).sum(0).reshape(b, s)

    def tfn(cfg):
        def fn(x):
            t = transports.from_config(cfg, jnp.float32)
            arena = x[0].reshape(b, s)
            return t(arena, jnp.zeros_like(arena),
                     jnp.zeros((b,), jnp.int32), (s,) * b)[0]
        return fn

    got = _run_on_mesh(mesh, tfn(FlareConfig(axes=("pod", "data"),
                                             sparse_k_frac=1.0)), xs)
    assert np.allclose(got, expect, atol=1e-4), \
        f"auto sparse on (3,4): {np.abs(got - expect).max()}"
    try:
        _run_on_mesh(mesh, tfn(FlareConfig(axes=("pod", "data"),
                                           sparse_k_frac=1.0,
                                           hierarchical=True)), xs)
    except ValueError as e:
        assert "power-of-two" in str(e), e
    else:
        raise AssertionError("forced hierarchical sparse on a non-pow2 "
                             "pod axis must raise")

    # the emulated switch data plane has no power-of-two constraint at
    # all: per-level merges are iterated folds and the non-pow2 levels
    # take the ring multicast — dense AND sparse innetwork reduce the
    # (3, 4) mesh correctly (the wire sparse transport cannot)
    for kw in (dict(), dict(sparse_k_frac=1.0)):
        got = _run_on_mesh(mesh, tfn(FlareConfig(axes=("pod", "data"),
                                                 transport="innetwork",
                                                 **kw)), xs)
        assert np.allclose(got, expect, atol=1e-4), \
            f"innetwork on (3,4) {kw}: {np.abs(got - expect).max()}"
    # small k + high threshold keeps coordinate lists sparse across BOTH
    # levels, so the merge itself crosses the non-pow2 pod axis
    kk = 4
    got = _run_on_mesh(mesh, tfn(FlareConfig(axes=("pod", "data"),
                                             transport="innetwork",
                                             sparse_k_frac=kk / s,
                                             density_threshold=0.9)), xs)

    def topk_np(v, n):
        i = np.argsort(-np.abs(v))[:n]
        o = np.zeros_like(v)
        o[i] = v[i]
        return o

    want = sum(np.stack([topk_np(np.asarray(xs[r]).reshape(b, s)[bi], kk)
                         for bi in range(b)]) for r in range(12))
    assert np.allclose(got, want, atol=1e-4), \
        f"innetwork sparse merge on (3,4): {np.abs(got - want).max()}"


CHILD_CHECKS = {
    "hier_vs_flat": (check_hier_matches_flat_psum, 8),
    "fixed_tree_bitwise": (check_fixed_tree_bitwise_device_permutation, 8),
    "sparse_nonpow2_fallback": (check_sparse_nonpow2_outer_fallback, 12),
}


@pytest.mark.parametrize("check", sorted(CHILD_CHECKS))
def test_hierarchical_multidevice(check):
    env = dict(os.environ)
    n = CHILD_CHECKS[check][1]
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    r = subprocess.run([sys.executable, __file__, check],
                       capture_output=True, text=True, timeout=900, env=env)
    assert r.returncode == 0, f"{check} failed:\n{r.stdout}\n{r.stderr}"
    assert "OK" in r.stdout


if __name__ == "__main__":
    _child_setup()
    CHILD_CHECKS[sys.argv[1]][0]()
    print(f"{sys.argv[1]} OK")
