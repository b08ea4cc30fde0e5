"""Property tests for the emulated switch data plane (``repro.switch``).

Three groups, all parent-side (the packet framing and the handlers are
pure local compute — only ``tests/multidevice_checks.py`` group
``switch`` needs the 8-device mesh):

* **Packet framing** — ``packetize``/``depacketize`` round-trips every
  dtype *bitwise* (random bit patterns, NaNs included) on ragged tails,
  and reassembly is header-driven, so any packet-order permutation
  round-trips identically.
* **Handlers** — the fixed-tree handler is bitwise-invariant under
  adversarial per-slot packet arrival permutations (the §6.3/F3 claim,
  executed by the actual ``kernels/tree_reduce`` combine); every §6
  buffer design computes the same sum; the int8 handler's fused
  dequant-accumulate matches its reference.
* **Model cross-validation** — the emulator's packet/combine/buffer
  counters (``dataplane.plan_counters``) are exactly the analytic
  model's inputs (``P``, ``N``, ``P−1`` combines, ``M`` buffers), and
  the sparse handler's *measured* collision count on real tensors
  matches the §7 hash-spill expectation the discrete-event simulator
  assumes (``switch_model.expected_hash_collisions``) — the functional
  and performance layers pinned to each other.
"""
import numpy as np
import pytest
from hypothesis import example, given, settings, strategies as st

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import sparse
from repro.perfmodel import switch_model as sm
from repro.perfmodel import switch_sim as ss
from repro.switch import dataplane, handlers as hd, packets as pk

DTYPES = ("float32", "float16", "bfloat16", "int32", "int8")


def _random_arena(rng: np.random.Generator, b: int, s: int, dtype):
    """Uniformly random *bit patterns* of the target dtype (NaNs and all)."""
    dt = jnp.dtype(dtype)
    bits = {1: np.uint8, 2: np.uint16, 4: np.uint32}[dt.itemsize]
    raw = jnp.asarray(rng.integers(0, np.iinfo(bits).max, size=(b, s),
                                   endpoint=True, dtype=bits))
    if jnp.issubdtype(dt, jnp.integer) and dt.itemsize == raw.dtype.itemsize:
        return raw.view(dt) if hasattr(raw, "view") else raw.astype(dt)
    return lax.bitcast_convert_type(raw, dt)


# ---------------------------------------------------------------------------
# Packet framing: bitwise round trip, ragged tails, permutation-proof.
# ---------------------------------------------------------------------------

@given(st.integers(1, 5), st.integers(1, 700), st.sampled_from(DTYPES),
       st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
@example(b=1, s=33, dtype="bfloat16", seed=0)   # signalling NaN at elem 32
def test_packet_roundtrip_bitwise(b, s, dtype, seed):
    rng = np.random.default_rng(seed)
    fmt = pk.PacketFormat(mtu_bytes=64)       # small MTU → ragged tails
    arena = _random_arena(rng, b, s, dtype)
    stream = pk.packetize(arena, fmt, child_rank=3)
    out = pk.depacketize(stream, fmt, b, s)
    assert out.dtype == arena.dtype
    assert np.asarray(out).tobytes() == np.asarray(arena).tobytes(), \
        f"round trip changed bits: B={b} S={s} {dtype}"

    # reassembly is header-driven: a permuted stream round-trips too
    perm = rng.permutation(stream.num_packets)
    shuffled = pk.PacketStream(stream.headers[perm], stream.payload[perm])
    out2 = pk.depacketize(shuffled, fmt, b, s)
    assert np.asarray(out2).tobytes() == np.asarray(arena).tobytes(), \
        "permuted stream reassembled differently"


@given(st.integers(1, 4), st.integers(1, 300), st.sampled_from(DTYPES))
@settings(max_examples=15, deadline=None)
def test_packet_headers(b, s, dtype):
    fmt = pk.PacketFormat(mtu_bytes=64)
    arena = jnp.zeros((b, s), jnp.dtype(dtype))
    stream = pk.packetize(arena, fmt, child_rank=7)
    hdr = np.asarray(stream.headers)
    e = fmt.payload_elems(dtype)
    npkt = fmt.packets_per_block(s, dtype)
    assert stream.num_packets == b * npkt
    assert (hdr[:, pk.HDR_CHILD] == 7).all()
    for blk in range(b):
        mine = hdr[hdr[:, pk.HDR_BLOCK] == blk]
        assert len(mine) == npkt
        # valid counts tile the block exactly; one completion marker
        assert mine[:, pk.HDR_VALID].sum() == s
        assert (mine[:, pk.HDR_VALID] <= e).all()
        assert mine[:, pk.HDR_LAST].sum() == 1
        assert mine[mine[:, pk.HDR_SEQ] == npkt - 1][0, pk.HDR_LAST] == 1


@given(st.integers(1, 5), st.integers(1, 700), st.sampled_from(DTYPES),
       st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
@example(b=1, s=33, dtype="bfloat16", seed=0)   # signalling NaN at elem 32
def test_frameplan_matches_per_packet_framing(b, s, dtype, seed):
    """The batched data plane's static FramePlan is a bitwise drop-in
    for per-packet framing (PR 7): ``pack`` produces exactly
    ``packetize().payload``, the static headers match the dynamic ones
    (all fields but the checksum — the batched plane verifies integrity
    via the fault schedule's static masks), ``unpack`` inverts ``pack``
    bit for bit on ragged tails and every dtype, and any slot arrival
    permutation is steered back to canonical order by (BLOCK, SEQ)
    alone."""
    rng = np.random.default_rng(seed)
    fmt = pk.PacketFormat(mtu_bytes=64)       # small MTU → ragged tails
    arena = _random_arena(rng, b, s, dtype)
    plan = pk.FramePlan(b, s, dtype, fmt)
    packed = plan.pack(arena)
    stream = pk.packetize(arena, fmt, child_rank=3)
    assert packed.shape == stream.payload.shape
    assert np.asarray(packed).tobytes() == \
        np.asarray(stream.payload).tobytes(), \
        f"pack != packetize payload: B={b} S={s} {dtype}"
    hdr = plan.headers(child_rank=3)
    dyn = np.asarray(stream.headers)
    for field in (pk.HDR_BLOCK, pk.HDR_SEQ, pk.HDR_CHILD, pk.HDR_VALID,
                  pk.HDR_LAST):
        assert np.array_equal(hdr[:, field], dyn[:, field]), field
    out = plan.unpack(packed)
    assert out.dtype == arena.dtype
    assert np.asarray(out).tobytes() == np.asarray(arena).tobytes(), \
        f"unpack(pack) changed bits: B={b} S={s} {dtype}"
    # arrival permutation: the (BLOCK, SEQ) fields alone recover the
    # canonical slot order — reshape-only reassembly stays sound
    perm = rng.permutation(plan.num_packets)
    hp = hdr[perm]
    order = np.argsort(hp[:, pk.HDR_BLOCK] * plan.packets_per_block
                       + hp[:, pk.HDR_SEQ])
    restored = np.asarray(packed)[perm][order]
    assert restored.tobytes() == np.asarray(packed).tobytes(), \
        "header steering failed to restore canonical slot order"


def test_frameplan_child_headers_stack():
    plan = pk.FramePlan(2, 100, jnp.float32, pk.PacketFormat(mtu_bytes=64))
    hdrs = plan.child_headers(5)
    assert hdrs.shape == (5, plan.num_packets, pk.HEADER_FIELDS)
    for p in range(5):
        assert (hdrs[p, :, pk.HDR_CHILD] == p).all()
        assert np.array_equal(hdrs[p, :, pk.HDR_BLOCK],
                              hdrs[0, :, pk.HDR_BLOCK])


# ---------------------------------------------------------------------------
# Handlers: arrival-order invariance (fixed tree) and design equivalence.
# ---------------------------------------------------------------------------

def _child_stack(rng, p, b, s, fmt, scale=1e3):
    """Stack P children's framed streams: (P, n, E) payload + headers."""
    arenas = [jnp.asarray((rng.normal(size=(b, s)) * scale)
                          .astype(np.float32)) for _ in range(p)]
    streams = [pk.packetize(a, fmt, child_rank=c)
               for c, a in enumerate(arenas)]
    payload = jnp.stack([st_.payload for st_ in streams])
    headers = jnp.stack([st_.headers for st_ in streams])
    return arenas, payload, headers


def _slot_perm(rng, p, n):
    """An adversarial per-packet-slot arrival permutation, shape (P, n)."""
    return jnp.asarray(np.stack([rng.permutation(p) for _ in range(n)],
                                axis=1), jnp.int32)


@given(st.integers(2, 9), st.integers(1, 3), st.integers(1, 130),
       st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_fixed_tree_handler_bitwise_arrival_invariance(p, b, s, seed):
    """The §6.3/F3 claim at handler level: the fixed-tree combine is a
    pure function of the child-rank headers — any packet arrival order
    (even interleaved per slot) produces identical bits."""
    rng = np.random.default_rng(seed)
    fmt = pk.PacketFormat(mtu_bytes=64)
    arenas, payload, headers = _child_stack(rng, p, b, s, fmt)
    h = hd.get_handler("fixed_tree")
    base, _ = hd.run(h, payload, headers, design="tree",
                     ctx={"dtype": jnp.float32})
    for _ in range(3):
        order = _slot_perm(rng, p, payload.shape[1])
        got, _ = hd.run(h, hd.apply_order(payload, order),
                        hd.apply_order(headers, order), design="tree",
                        ctx={"dtype": jnp.float32})
        assert np.asarray(got).tobytes() == np.asarray(base).tobytes(), \
            f"arrival permutation changed bits: P={p} B={b} S={s}"
    # and the combine is correct against an fp64 oracle
    want = np.sum([np.asarray(a, np.float64) for a in arenas], axis=0)
    got = pk.depacketize(pk.PacketStream(headers[0], base), fmt, b, s)
    scale = max(np.abs(want).max(), 1.0)
    assert np.allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6 * scale)


@given(st.integers(2, 8), st.integers(1, 100), st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_buffer_designs_same_sum(p, s, seed):
    """§6.1–§6.3 designs differ in contention/memory, not arithmetic:
    every fold computes the same sum (within fp reassociation)."""
    rng = np.random.default_rng(seed)
    stack = jnp.asarray(rng.normal(size=(p, 2, s)).astype(np.float32))
    want = np.asarray(stack, np.float64).sum(0)
    for design, n_bufs in [("single", 1), ("multi", 2), ("multi", 4),
                           ("tree", 1)]:
        got = np.asarray(hd.fold(stack, design, n_bufs))
        assert np.allclose(got, want, rtol=1e-5, atol=1e-4), (design, n_bufs)


def test_integer_dense_handler_exact():
    """Integer arenas aggregate in their native dtype — 2^24 + 1 summed
    four times must not round through an fp32 accumulation buffer."""
    stack = jnp.full((4, 1, 8), (1 << 24) + 1, jnp.int32)
    h = hd.get_handler("dense_sum")
    for design in ("single", "multi", "tree"):
        got, _ = hd.run(h, stack, None, design=design, n_bufs=2,
                        ctx={"dtype": jnp.int32})
        assert got.dtype == jnp.int32
        assert (np.asarray(got) == 4 * ((1 << 24) + 1)).all(), design


def test_int8_handler_matches_reference():
    """The fused dequant-accumulate kernel == dequantize-then-fold, and
    all designs agree within reassociation error."""
    from repro.core import compression
    rng = np.random.default_rng(3)
    p, n, block = 5, 1024, 256
    x = rng.normal(size=(p, n)).astype(np.float32)
    q, scales = compression.quantize_int8(jnp.asarray(x), block)
    want = np.asarray(compression.dequantize_int8(q, scales, block)).sum(0)
    payload = {"q": q.reshape(p, 4, 256), "scale": scales.reshape(p, 4, 1)}
    h = hd.get_handler("int8_dequant")
    for design in ("single", "multi", "tree"):
        got, _ = hd.run(h, payload, None, design=design, n_bufs=2,
                        ctx={"qblock": block})
        assert np.allclose(np.asarray(got).reshape(n), want, atol=1e-4), \
            design
    # the fused Pallas kernel == the pure-jnp reference oracle (same
    # sequential fold; bits may differ by one compiler-fused mul-add)
    from repro.kernels import ops, ref
    fused = np.asarray(ops.dequant_accum(q, scales, qblock=block))
    oracle = np.asarray(ref.dequant_accum(q, scales, block))
    np.testing.assert_allclose(fused, oracle, rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError, match="qblock"):
        ops.dequant_accum(q[:, :1000], scales, qblock=block)


def test_sparse_handler_merges_and_counts():
    """The coordinate-merge handler accumulates duplicates and reports
    exactly the duplicate count as collisions."""
    idx = jnp.asarray([[[0, 2, 4, sparse.SENTINEL]],
                       [[2, 3, sparse.SENTINEL, sparse.SENTINEL]],
                       [[0, 2, 5, 6]]], jnp.int32)          # (P=3, B=1, 4)
    val = jnp.ones_like(idx, jnp.float32)
    val = jnp.where(idx != sparse.SENTINEL, val, 0.0)
    h = hd.get_handler("sparse_merge")
    merged, stats = hd.run(h, {"idx": idx, "val": val}, None,
                           design="single")
    dense = np.asarray(sparse.scatter_dense(merged["val"][0],
                                            merged["idx"][0], 8))
    assert np.array_equal(dense, [2, 0, 3, 1, 1, 1, 1, 0])
    assert int(stats["collisions"]) == 3        # 2 (+1 at idx 0, +2 at idx 2)


# ---------------------------------------------------------------------------
# Cross-validation: emulator counters ↔ perfmodel.switch_model.
# ---------------------------------------------------------------------------

def test_plan_counters_match_switch_model_inputs():
    """The plane's static counters are the analytic model's inputs."""
    b, s = 3, 2048
    c = dataplane.plan_counters(("pod", "data"), (2, 4), b, s, jnp.float32)
    fmt = dataplane.DEFAULT_FORMAT
    assert c.payload_elems == fmt.payload_elems(jnp.float32)    # N
    assert c.packet_bytes == fmt.mtu_bytes
    npkt = fmt.packets_per_block(s, jnp.float32)
    assert c.blocks == b * npkt
    # §6.4 switchover: 8 KiB blocks < 128 KiB → tree aggregation
    assert (c.design, c.n_bufs) == sm.select_design(s * 4)
    for lvl, fanin in zip(c.levels, (4, 2)):
        assert lvl.fanin == fanin                               # P
        assert lvl.ingress_packets == c.blocks * fanin
        assert lvl.egress_packets == c.blocks
        # every §6 service time amortizes exactly P−1 combines per block
        assert lvl.combines == c.blocks * (fanin - 1)
        assert lvl.buffers_per_block == sm.buffers_per_block(
            c.design, fanin, c.n_bufs)                          # M
    # the model evaluates cleanly at the emulator's operating point
    pt = c.model_point(b * s * 4)
    assert pt.bandwidth_tbps > 0 and pt.working_memory_bytes > 0
    # reproducible mode pins tree aggregation at any size (§6.4)
    big = dataplane.plan_counters(("data",), (8,), 1, 1 << 20, jnp.float32,
                                  reproducible=True)
    assert big.design == "tree"
    assert sm.select_design(4 << 20)[0] != "tree"


def test_counters_invariant_under_batched_schedule():
    """Batching changes the *schedule* of the emulation, never the
    modeled switch work: the same packets arrive, the same combines
    run, the same buffers hold them — so the analytic counters are
    identical for the batched plane and the slot-loop oracle, for both
    the mesh-axis and rebuilt-tree variants."""
    from repro.core import topology
    for kw in (dict(), dict(reproducible=True), dict(design="single")):
        a = dataplane.plan_counters(("pod", "data"), (2, 4), 3, 2048,
                                    jnp.float32, batched=True, **kw)
        b = dataplane.plan_counters(("pod", "data"), (2, 4), 3, 2048,
                                    jnp.float32, batched=False, **kw)
        assert a == b, kw
    tree = topology.build_tree(8, 4)
    ta = dataplane.tree_counters(tree, 2, 1024, jnp.float32, batched=True)
    tb = dataplane.tree_counters(tree, 2, 1024, jnp.float32, batched=False)
    assert ta == tb


@pytest.mark.parametrize("seed", [0, 1])
def test_sparse_collisions_match_hash_model(seed):
    """Measured collisions from merging P real top-k lists match the §7
    hash-table expectation the DES simulator's spill model assumes."""
    p_children, s, k = 8, 4096, 256
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(p_children, 1, s)).astype(np.float32))
    vals, idxs = [], []
    for c in range(p_children):
        v, i = sparse.topk_sparsify(x[c, 0], k)
        vals.append(v[None])
        idxs.append(i[None])
    payload = {"idx": jnp.stack(idxs), "val": jnp.stack(vals)}
    h = hd.get_handler("sparse_merge")
    _, stats = hd.run(h, payload, None, design="single")
    actual = int(stats["collisions"])
    expected = sm.expected_hash_collisions(p_children * k, s)
    assert expected > 0
    assert 0.5 * expected < actual < 1.8 * expected, (actual, expected)
    # spill traffic conversion: one (idx, val) pair per collision
    assert sm.expected_hash_spill_bytes(p_children * k, s) == \
        pytest.approx(expected * 8)


def test_des_simulator_uses_shared_spill_formula():
    """switch_sim's extra_traffic_bytes is the shared expectation,
    applied per completed block — the emulator, the DES simulator and
    the analytic model all read the same §7 spill curve."""
    params = sm.SwitchParams()
    density = 0.01
    r = ss.simulate("single", 1 << 20, params, P=64, sparse_density=density)
    elems = (params.packet_bytes // 2) // params.elem_bytes
    span = elems / density
    per_block = sm.expected_hash_spill_bytes(64 * elems, span,
                                             params.elem_bytes)
    assert r.blocks_completed > 0
    assert r.extra_traffic_bytes == int(per_block) * r.blocks_completed


def test_sparse_densify_on_overflow_bitwise(mesh_shape):
    """Direct unit test of the §7 densify-on-overflow path in
    ``switch/dataplane.py`` (PR 4 exercised it only incidentally): a
    tiny list budget forces overflow at the leaf and — on the two-level
    shape — mid-tree, and the result must be **bitwise equal** to the
    dense handler run on the same (host- or leaf-merged) lists.  Runs
    under 8 fake devices in a subprocess (same pattern as the
    multidevice groups) for both ``--mesh-shape`` topologies."""
    import os
    import subprocess
    import sys

    script = os.path.join(os.path.dirname(__file__),
                          "multidevice_checks.py")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    env["REPRO_MESH_SHAPE"] = mesh_shape
    r = subprocess.run([sys.executable, script, "sparse_densify"],
                       capture_output=True, text=True, timeout=900, env=env)
    assert r.returncode == 0, \
        f"sparse_densify failed:\n{r.stdout}\n{r.stderr}"
    assert "OK" in r.stdout


# ---------------------------------------------------------------------------
# Reliability layer (PR 6): exactly-once ingress under any surviving plan.
# ---------------------------------------------------------------------------

@given(st.integers(2, 8), st.integers(1, 2), st.integers(1, 100),
       st.sampled_from(("float32", "int32", "int8")),
       st.floats(0.0, 0.15), st.floats(0.0, 0.4), st.floats(0.0, 0.6),
       st.floats(0.0, 0.08), st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_reliable_ingress_bitwise_under_any_surviving_plan(
        p, b, s, dtype, drop, dup, reorder, corrupt, seed):
    """DESIGN.md §14 as a property: for ANY fault plan whose retries
    succeed within the budget, the reliability layer reconstructs the
    clean canonical child stack bit for bit — drops are retransmitted,
    duplicate deliveries are admitted at most once (the seen-bitmap:
    they can never double-count), corrupted deliveries are rejected by
    the payload checksum, and reordered streams are steered back by the
    CHILD header.  The traced counters equal the static schedule
    exactly; a plan past the budget must refuse at trace time."""
    rng = np.random.default_rng(seed)
    fmt = pk.PacketFormat(mtu_bytes=64)
    arenas = [_random_arena(rng, b, s, dtype) for _ in range(p)]
    streams = [pk.packetize(a, fmt, child_rank=c)
               for c, a in enumerate(arenas)]
    payload = jnp.stack([st_.payload for st_ in streams])
    headers = jnp.stack([st_.headers for st_ in streams])
    n = payload.shape[1]
    plan = pk.FaultPlan(seed=seed, drop=drop, duplicate=dup,
                        reorder=reorder, corrupt=corrupt)
    sched = plan.schedule(0, p, n)
    stats = dataplane._new_fault_stats()
    if not sched.survives:
        with pytest.raises(dataplane.FaultBudgetExceeded):
            dataplane._reliable_ingress(payload, headers, sched, stats)
        return
    got, got_hdr = dataplane._reliable_ingress(payload, headers, sched,
                                               stats)
    assert np.asarray(got).tobytes() == np.asarray(payload).tobytes(), \
        f"surviving plan changed bits: P={p} B={b} S={s} {dtype}"
    assert np.asarray(got_hdr).tobytes() == np.asarray(headers).tobytes()
    assert int(stats["retransmits"]) == sched.retransmits
    assert int(stats["duplicates_dropped"]) == sched.duplicates
    assert int(stats["corrupt_rejected"]) == sched.corrupt_rejected
    assert int(stats["delivered"]) == p * n


@given(st.integers(2, 6), st.integers(1, 120), st.floats(0.0, 0.1),
       st.floats(0.0, 0.3), st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_reliable_ingress_sideband_fate_shares(p, s, drop, corrupt, seed):
    """The int8 plane's scales sideband rides the checksummed ``q``
    stream's accept mask (headers steer both): any surviving plan
    restores *both* leaves of the payload pytree bitwise."""
    rng = np.random.default_rng(seed)
    fmt = pk.PacketFormat(mtu_bytes=64)
    e = fmt.payload_elems(jnp.int8)
    sfmt = pk.PacketFormat(mtu_bytes=4)          # one fp32 scale per packet
    qs, ss_ = [], []
    for c in range(p):
        q = _random_arena(rng, 1, s, "int8")
        sc = jnp.asarray(rng.normal(size=(1, -(-s // e)))
                         .astype(np.float32))
        qs.append(pk.packetize(q, fmt, child_rank=c))
        ss_.append(pk.packetize(sc, sfmt, child_rank=c))
    payload = {"q": jnp.stack([t.payload for t in qs]),
               "scale": jnp.stack([t.payload for t in ss_])}
    headers = jnp.stack([t.headers for t in qs])
    n = payload["q"].shape[1]
    assert payload["scale"].shape[1] == n        # sideband packet-aligned
    plan = pk.FaultPlan(seed=seed, drop=drop, corrupt=corrupt)
    sched = plan.schedule(0, p, n)
    stats = dataplane._new_fault_stats()
    if not sched.survives:
        with pytest.raises(dataplane.FaultBudgetExceeded):
            dataplane._reliable_ingress(payload, headers, sched, stats)
        return
    got, _ = dataplane._reliable_ingress(payload, headers, sched, stats)
    for key in ("q", "scale"):
        assert np.asarray(got[key]).tobytes() == \
            np.asarray(payload[key]).tobytes(), key


def test_single_buffer_fold_is_order_sensitive_but_tree_is_not():
    """Sanity for the reproducibility story: the contended single buffer
    (§6.1) folds in arrival order — permuting arrivals may change bits —
    while the fixed tree cannot (asserted exhaustively above)."""
    rng = np.random.default_rng(11)
    stack = jnp.asarray((rng.normal(size=(8, 1, 64)) * 1e3)
                        .astype(np.float32))
    perm = jnp.asarray(rng.permutation(8), jnp.int32)
    a = np.asarray(hd.fold_single(stack))
    bb = np.asarray(hd.fold_single(stack[perm]))
    assert np.allclose(a, bb, rtol=1e-4, atol=1e-2)     # same sum...
    assert a.tobytes() != bb.tobytes()                  # ...different bits
    t0 = np.asarray(hd.fold_tree(stack.astype(jnp.float32)))
    # fold_tree keys on stack position; the *handler* restores child
    # order from headers first — at fold level the claim is determinism
    t1 = np.asarray(hd.fold_tree(stack.astype(jnp.float32)))
    assert t0.tobytes() == t1.tobytes()
