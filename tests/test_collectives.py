"""Multi-device collective tests (subprocess with 8 fake CPU devices).

The dry-run owns the 512-device flag and the rest of the suite must see
one device, so every multi-device check runs in its own subprocess.
"""
import os
import subprocess
import sys

import pytest

from multidevice_checks import RING_CLASS_CHECKS, TRANSPORT_COUNT_CHECKS

_SCRIPT = os.path.join(os.path.dirname(__file__), "multidevice_checks.py")


def _run_group(group: str, mesh_shape: str | None = None, devices: int = 8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    if mesh_shape is not None:
        env["REPRO_MESH_SHAPE"] = mesh_shape
    r = subprocess.run([sys.executable, _SCRIPT, group],
                       capture_output=True, text=True, timeout=900, env=env)
    assert r.returncode == 0, f"{group} failed:\n{r.stdout}\n{r.stderr}"
    return r.stdout


@pytest.mark.parametrize("group", ["collectives", "arena_pipeline",
                                   "sparse_quant", "transports",
                                   "fsdp_engine", "trainer", "repro"])
def test_multidevice(group):
    out = _run_group(group)
    assert "OK" in out


@pytest.fixture(scope="module")
def ring_classes_out():
    """One four-device run of every stagger-class ring check."""
    return _run_group("ring_classes", devices=4)


@pytest.mark.parametrize("case", RING_CLASS_CHECKS)
def test_ring_stagger_classes(ring_classes_out, case):
    """The batched ring that picks chunks by stagger class: bitwise-equal
    to the per-bucket ring (looped and scanned), with one ppermute per
    round and no gather or scatter."""
    assert f"ring_classes {case} OK" in ring_classes_out


@pytest.fixture(scope="module")
def transport_counts_out():
    """One eight-device run of every transport-count and layout check."""
    return _run_group("transport_counts")


@pytest.mark.parametrize("case", TRANSPORT_COUNT_CHECKS)
def test_transport_collective_counts(transport_counts_out, case):
    """Each wire schedule's compiled collective counts, by kind, are the
    same at B = 4 and B = 8 and what its docstring states; a
    reproducible reducer's bits do not depend on ``bucket_bytes``."""
    assert f"transport_counts {case} OK" in transport_counts_out


def test_multidevice_hierarchy(mesh_shape):
    """Shape-parametric: flat (8) and two-level (2x4) topologies, both in
    one tier-1 run (conftest ``--mesh-shape``)."""
    out = _run_group("hierarchy", mesh_shape=mesh_shape)
    assert "OK" in out


def test_multidevice_switch(mesh_shape):
    """The emulated switch data plane (PR 4): innetwork == flat ==
    hierarchical per handler type, fixed-tree bitwise claims, sparse
    counter cross-check — under both mesh shapes."""
    out = _run_group("switch", mesh_shape=mesh_shape)
    assert "OK" in out


def test_multidevice_runtime(mesh_shape):
    """The multi-tenant switch runtime (PR 5): three heterogeneous
    tenants share one emulated switch under adversarial packet
    interleavings, each bitwise-equal to its solo run; shared-switch
    model ↔ scheduler cross-check — under both mesh shapes."""
    out = _run_group("runtime", mesh_shape=mesh_shape)
    assert "OK" in out


def test_multidevice_canary(mesh_shape):
    """Congestion-aware dynamic trees (PR 8, DESIGN.md §15): a hot leaf
    slot triggers a replan onto the cheapest tree, the reproducible
    fixed-tree canary tenant stays bitwise identical across the rebind,
    the replan is idempotent under a static map, and model ↔ measured
    agree at the congested operating point — under both mesh shapes."""
    out = _run_group("canary", mesh_shape=mesh_shape)
    assert "OK" in out


def test_multidevice_obs(mesh_shape):
    """The flight recorder (PR 9, DESIGN.md §16): two tenants under one
    counting-clock telemetry handle export byte-identical trace/metrics
    JSON across independent runs, attaching telemetry never changes the
    reduction bits, and every exported counter is integer-equal to its
    static source (``tree_counters`` / ``FaultSchedule``) — under both
    mesh shapes."""
    out = _run_group("obs", mesh_shape=mesh_shape)
    assert "OK" in out


@pytest.mark.health
def test_multidevice_health(mesh_shape):
    """The fabric health plane (PR 10, DESIGN.md §17): the fault-storm
    detector fires counter-exact incidents on an injected FaultPlan, the
    drift detector's SLO-dispatched replan leaves the manager bitwise
    identical to the manual PR 8 call (tree, sessions, reduction bits),
    and two independent watched runs under counting clocks export
    byte-identical incident logs — under both mesh shapes."""
    out = _run_group("health", mesh_shape=mesh_shape)
    assert "OK" in out


@pytest.mark.chaos
def test_multidevice_chaos(mesh_shape):
    """The lossy-fabric reliability layer (PR 6, DESIGN.md §14): dense /
    int8 / sparse planes under deterministic drop + duplicate + reorder +
    corrupt injection stay bitwise-equal to the fault-free run while the
    retry budget holds; traced retry counters equal the static schedule;
    budget exhaustion degrades only the affected session to the wire —
    under both mesh shapes.  All fault seeds are fixed (deterministic
    seed search inside the check)."""
    out = _run_group("chaos", mesh_shape=mesh_shape)
    assert "OK" in out
