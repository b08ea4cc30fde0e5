"""Host-side tests for the flight recorder (``repro.obs``, DESIGN.md §16).

All pure control-plane Python on one device — the tensor-level claims
(byte-identical exports across traced runs, telemetry neutrality on the
reduction bits) run on the 8-device mesh in ``tests/multidevice_checks.py``
group ``obs``.  Covered here:

* the typed registry (strict kinds, monotone counters, deterministic
  export, traced-value rejection);
* the tracer (injected counting clock → byte-stable Chrome JSON, ring
  flight-recorder mode, span nesting errors);
* the structured ``ManagerReport`` (satellite: field pinning — the
  admissions/evictions/replan audit trail and per-tenant shares — plus
  the byte-stable legacy ``str()`` rendering);
* the congestion regression: a monitor fed from registry gauges yields
  the *identical* ``CongestionMap`` as one fed from raw schedules;
* counter integer-equality against ``plan_counters`` and static
  ``FaultSchedule``s (the host half of the determinism satellite);
* the ``python -m repro.obs.report`` summary CLI;
* config neutrality: ``FlareConfig(telemetry=)`` never changes equality
  or the jit cache key (hash).
"""
import json

import pytest

import jax
import jax.numpy as jnp

from repro.core.engine import FlareConfig
from repro.obs import (ManagerReport, MetricsRegistry, Telemetry,
                       TenantReport, Tracer, counting_clock, slot_name)
from repro.obs import report as obs_report
from repro.runtime import CongestionMonitor, SessionManager
from repro.switch import dataplane
from repro.switch.packets import FaultPlan


def _mgr(**kw):
    return SessionManager(("pod", "data"), (2, 4), **kw)


def _open_two(mgr):
    mgr.open("a", mode="dense", num_buckets=2, bucket_elems=256,
             dtype=jnp.float32)
    mgr.open("b", mode="sparse", num_buckets=2, bucket_elems=512,
             dtype=jnp.float32, k=16)


# ---------------------------------------------------------------------------
# Registry.
# ---------------------------------------------------------------------------

def test_registry_instruments_and_strict_kinds():
    reg = MetricsRegistry()
    assert reg.counter("a.pkts").inc(3) == 3
    assert reg.counter("a.pkts").inc() == 4       # create-or-get
    reg.gauge("a.level").set(0.5)
    reg.gauge("a.level").set(1.5)                 # last-write-wins
    reg.histogram("a.dur").record(2.0)
    reg.histogram("a.dur").record(4.0)
    assert reg.value("a.pkts") == 4
    assert reg.value("a.level") == 1.5
    assert reg.value("a.missing", default=7) == 7
    assert "a.pkts" in reg and "a.missing" not in reg
    assert reg.names("a.") == ["a.dur", "a.level", "a.pkts"]
    h = reg.histogram("a.dur")
    assert (h.count, h.sum, h.min, h.max, h.mean) == (2, 6.0, 2.0, 4.0, 3.0)
    with pytest.raises(TypeError, match="is a counter"):
        reg.gauge("a.pkts")
    with pytest.raises(ValueError, match="cannot decrease"):
        reg.counter("a.pkts").inc(-1)


def test_registry_rejects_traced_values():
    """The overhead contract's teeth: a counter fed from inside a traced
    program fails loudly instead of silently adding ops."""
    reg = MetricsRegistry()

    def leak(x):
        reg.counter("bad").inc(x)
        return x

    with pytest.raises(TypeError, match="concrete host scalars"):
        jax.make_jaxpr(leak)(jnp.int32(1))


def test_registry_export_deterministic():
    def build():
        reg = MetricsRegistry()
        reg.counter("z.late").inc(2)
        reg.gauge("a.early").set(1.0)
        reg.observe_tree("plane.t", {"retransmits": jnp.int32(5),
                                     "delivered": 9})
        return reg

    a, b = build(), build()
    assert a.to_json() == b.to_json()
    assert list(a.as_dict()) == sorted(a.as_dict())
    assert a.value("plane.t.retransmits") == 5
    assert a.value("plane.t.delivered") == 9


# ---------------------------------------------------------------------------
# Tracer.
# ---------------------------------------------------------------------------

def _trace_build():
    tr = Tracer(clock=counting_clock())
    with tr.span("plane.l1", track="plane/t", process="trace",
                 args={"fanin": 4}):
        tr.instant("plane.retry.l1", track="plane/t", process="trace",
                   args={"rounds": 2})
    tr.span_at("model.drain", 0.0, 12.5, track="model/t",
               args={"packets": 64})
    return tr


def test_tracer_chrome_export_byte_stable():
    a, b = _trace_build(), _trace_build()
    assert a.to_json() == b.to_json()
    doc = json.loads(a.to_json(metrics={"m": {"type": "counter",
                                              "value": 1}}))
    evs = doc["traceEvents"]
    assert doc["metrics"] == {"m": {"type": "counter", "value": 1}}
    procs = {e["args"]["name"] for e in evs
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert {"trace", "modeled"} <= procs
    phs = [e["ph"] for e in evs]
    assert "X" in phs and "i" in phs
    x = [e for e in evs if e["ph"] == "X" and e["name"] == "plane.l1"][0]
    assert x["args"] == {"fanin": 4} and x["dur"] > 0


def test_measured_spans_reach_the_profiler_trace(tmp_path):
    """Spans and instants on "measured" also sit on the host plane of a
    JAX profiler trace, on the device ops' clock; other processes do
    not."""
    import glob

    import jax
    from jax.profiler import ProfileData

    tr = Tracer(clock=counting_clock())
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("obs.profiled_step", track="steps"):
            tr.instant("obs.profiled_instant")
            jax.block_until_ready(jax.numpy.ones(4) * 2)
        tr.begin("obs.trace_only", process="trace")
        tr.end()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                            "*.xplane.pb"))
    events = {e.name: e for p in ProfileData.from_file(path).planes
              if p.name.startswith("/host:") for line in p.lines
              for e in line.events}
    assert {"obs.profiled_step", "obs.profiled_instant"} <= set(events)
    assert "obs.trace_only" not in events
    step, instant = events["obs.profiled_step"], events["obs.profiled_instant"]
    assert step.start_ns <= instant.start_ns <= step.start_ns + \
        step.duration_ns
    # the export is the tracer's own, untouched by the profiler
    assert [e["name"] for e in tr.events] == [
        "obs.profiled_instant", "obs.profiled_step", "obs.trace_only"]


def test_tracer_ring_keeps_last_events():
    tr = Tracer(clock=counting_clock(), ring=2)
    for i in range(5):
        tr.instant(f"e{i}")
    names = [e["name"] for e in json.loads(tr.to_json())["traceEvents"]
             if e.get("ph") == "i"]
    assert names == ["e3", "e4"]


def test_tracer_end_without_begin_raises():
    tr = Tracer(clock=counting_clock())
    with pytest.raises(RuntimeError, match="without a matching begin"):
        tr.end()


# ---------------------------------------------------------------------------
# ManagerReport (satellite: field pinning + byte-stable legacy string).
# ---------------------------------------------------------------------------

def test_manager_report_idle_string_pinned():
    rep = _mgr().report()
    assert isinstance(rep, ManagerReport)
    assert rep.tenants == () and rep.sessions == 0
    assert str(rep) == "switch idle: no sessions"


def test_manager_report_fields_pinned():
    mgr = _mgr(max_sessions=4)
    _open_two(mgr)
    mgr.open("c", mode="int8", num_buckets=1, bucket_elems=256,
             dtype=jnp.float32)
    assert mgr.evict("c", reason="testing the audit trail")
    mon = CongestionMonitor(mgr)
    res = mgr.replan(mon, threshold=0.5, hysteresis=0.05)

    rep = mgr.report()
    assert isinstance(rep, ManagerReport)
    # the audit surface the legacy string never carried
    assert rep.admissions == 3
    assert rep.evictions == (("c", "testing the audit trail"),)
    assert rep.replans == ((res.replanned, res.reason),)
    assert rep.replan_reasons == (res.reason,)
    # per-tenant typed rows: every live session, shares a partition of 1
    assert [t.tenant for t in rep.tenants] == ["a", "b"]
    for t in rep.tenants:
        assert isinstance(t, TenantReport)
        assert t.packets > 0 and t.combines > 0
        assert t.demand_bytes > 0 and t.clusters >= 1
        assert t.bottleneck in ("compute", "line")
        assert 0.0 < t.share <= 1.0
    assert sum(t.share for t in rep.tenants) == pytest.approx(1.0)
    by = {t.tenant: t for t in rep.tenants}
    assert by["a"].mode == "dense" and by["b"].mode == "sparse"
    assert (by["a"].num_buckets, by["a"].bucket_elems) == (2, 256)
    assert by["a"].retransmits == 0


def test_manager_report_string_matches_legacy_format():
    mgr = _mgr()
    _open_two(mgr)
    rep = str(mgr.report())
    head, *rows = rep.splitlines()
    assert head.startswith("switch: ") and "2/8 sessions" in head
    assert "policy=" in head and "order=" in head
    assert len(rows) == 2
    for row in rows:
        assert "pkt/cy" in row and "-bound)" in row
        assert "measured=" in row and "predicted=" in row
    # rendering is a pure function of the dataclass: byte-stable
    assert str(mgr.report()) == rep


def test_lossy_session_report_carries_retransmits():
    mgr = _mgr()
    mgr.open("t", mode="dense", num_buckets=4, bucket_elems=256,
             dtype=jnp.float32, fault_plan=FaultPlan(seed=1, drop=0.2))
    rep = mgr.report()
    assert rep.tenants[0].retransmits == mgr.session("t").retransmit_packets
    assert rep.tenants[0].retransmits > 0


# ---------------------------------------------------------------------------
# Congestion regression: registry gauges ≡ raw schedules (satellite).
# ---------------------------------------------------------------------------

def test_congestion_monitor_registry_equals_raw():
    tm = Telemetry.create(clock=counting_clock())
    mgr = _mgr(telemetry=tm)
    _open_two(mgr)
    mgr.schedule()                 # publishes the schedule.* gauges
    assert "schedule.makespan_cycles" in tm.registry

    raw = CongestionMonitor(mgr)
    fed = CongestionMonitor(mgr, registry=tm.registry)
    for mon in (raw, fed):
        mon.inject((1, 0), 2.0)
    assert fed.observe().hotness == raw.observe().hotness
    assert fed.observe().peak() == raw.observe().peak()
    # hotness lands in the registry too (manager's telemetry attached)
    assert tm.registry.value(
        f"congestion.{slot_name(1, 0)}.hotness") == \
        raw.observe().of((1, 0))


def test_congestion_monitor_registry_idle_manager():
    """With no published gauges the registry-fed monitor falls back to
    the raw derivation — never a crash, never a different map."""
    mgr = _mgr()
    _open_two(mgr)
    fed = CongestionMonitor(mgr, registry=MetricsRegistry())
    raw = CongestionMonitor(mgr)
    assert fed.observe().hotness == raw.observe().hotness


# ---------------------------------------------------------------------------
# Counter integer-equality against the static sources (host half).
# ---------------------------------------------------------------------------

def test_switch_counters_integer_equal_to_plan_counters():
    tm = Telemetry.create()
    pc = dataplane.plan_counters(("data",), (8,), 3, 2048, jnp.float32)
    tm.record_switch_counters("t", pc)
    reg = tm.registry
    for i, lvl in enumerate(pc.levels):
        pre = f"switch.t.l{i + 1}"
        assert reg.value(f"{pre}.ingress_packets") == lvl.ingress_packets
        assert reg.value(f"{pre}.egress_packets") == lvl.egress_packets
        assert reg.value(f"{pre}.combines") == lvl.combines
        for name in (f"{pre}.ingress_packets", f"{pre}.combines"):
            assert reg.get(name).kind == "counter"
    assert reg.value("switch.t.blocks") == pc.blocks
    assert reg.value("switch.t.total_combines") == pc.total_combines


def test_fault_schedule_counters_integer_equal():
    plan = FaultPlan(seed=1, drop=0.05, duplicate=0.2)
    counts = dataplane.level_packet_counts([4, 2], 3, 512, jnp.float32)
    scheds = [s for s in dataplane.fault_schedules(plan, counts)
              if s is not None]
    assert scheds, "plan must apply to at least one level"
    tm = Telemetry.create()
    tm.record_fault_schedules("t", dataplane.fault_schedules(plan, counts))
    reg = tm.registry
    assert reg.value("tenant.t.retransmits") == \
        sum(s.retransmits for s in scheds)
    assert reg.value("tenant.t.retry_rounds") == \
        sum(max(0, s.rounds - 1) for s in scheds)
    assert reg.value("tenant.t.wait_rounds") == \
        sum(int(round(s.wait_rounds)) for s in scheds)
    assert reg.value("tenant.t.duplicates") == \
        sum(s.duplicates for s in scheds)
    assert reg.value("tenant.t.corrupt_rejected") == \
        sum(s.corrupt_rejected for s in scheds)
    # fault-free sessions never grow reliability counters
    tm2 = Telemetry.create()
    tm2.record_fault_schedules("t", [None, None])
    assert tm2.registry.names() == []


def test_admission_records_once_per_session():
    """Counters are written at admission, never on re-attach: the same
    tenant traced twice must not double its static counters."""
    tm = Telemetry.create(clock=counting_clock())
    mgr = _mgr(telemetry=tm)
    _open_two(mgr)
    once = tm.registry.value("switch.a.l1.ingress_packets")
    again = mgr.attach("a", mode="dense", num_buckets=2, bucket_elems=256,
                       dtype=jnp.float32)
    assert again is mgr.session("a")
    assert tm.registry.value("switch.a.l1.ingress_packets") == once
    assert tm.registry.value("manager.admissions") == 2


# ---------------------------------------------------------------------------
# Export + the summary CLI.
# ---------------------------------------------------------------------------

def _exported(tmp_path):
    tm = Telemetry.create(clock=counting_clock())
    mgr = _mgr(telemetry=tm)
    _open_two(mgr)
    mgr.schedule()
    CongestionMonitor(mgr, registry=tm.registry).observe()
    mpath, tpath = str(tmp_path / "m.json"), str(tmp_path / "t.json")
    tm.export_metrics(mpath)
    tm.export_trace(tpath)
    return mpath, tpath


def test_export_artifacts_are_valid_json(tmp_path):
    mpath, tpath = _exported(tmp_path)
    with open(mpath) as f:
        metrics = json.load(f)
    with open(tpath) as f:
        trace = json.load(f)
    assert any(n.startswith("tenant.a.sched.") for n in metrics)
    assert any(n.startswith("congestion.") for n in metrics)
    assert trace["metrics"] == metrics
    assert any(e.get("name") == "session.admit"
               for e in trace["traceEvents"])


def test_report_cli_renders_tables(tmp_path, capsys):
    mpath, tpath = _exported(tmp_path)
    assert obs_report.main([mpath, tpath]) == 0
    out = capsys.readouterr().out
    assert "== per-tenant ==" in out
    assert "== per-slot congestion ==" in out
    for tenant in ("a", "b"):
        assert f"\n{tenant}" in out
    assert slot_name(1, 0) in out
    assert "spans on" in out and "tracks ==" in out


def test_report_cli_reads_metrics_from_trace(tmp_path, capsys):
    _, tpath = _exported(tmp_path)
    assert obs_report.main([tpath]) == 0
    out = capsys.readouterr().out
    assert "no per-tenant metrics" not in out


# ---------------------------------------------------------------------------
# Histogram percentiles (PR 10 satellite).
# ---------------------------------------------------------------------------

def test_histogram_percentiles_nearest_rank():
    reg = MetricsRegistry()
    h = reg.histogram("h")
    for v in range(1, 101):
        h.record(float(v))
    assert h.percentile(50.0) == 50.0
    assert h.percentile(95.0) == 95.0
    assert h.percentile(99.0) == 99.0
    assert h.percentile(0.0) == 1.0          # nearest-rank floor: rank 1
    assert h.percentile(100.0) == 100.0
    snap = h.snapshot()
    assert (snap["p50"], snap["p95"], snap["p99"]) == (50.0, 95.0, 99.0)
    with pytest.raises(ValueError, match=r"\[0, 100\]"):
        h.percentile(101.0)


def test_histogram_percentiles_empty_and_order_insensitive():
    h = MetricsRegistry().histogram("h")
    assert h.percentile(50.0) is None
    assert h.snapshot()["p99"] is None
    for v in (9.0, 1.0, 5.0):                # unsorted ingest
        h.record(v)
    assert h.percentile(50.0) == 5.0


def test_histogram_sample_cap_keeps_first_window():
    h = MetricsRegistry().histogram("h")
    h.SAMPLE_CAP = 4                         # shadow the class bound
    for v in range(10):
        h.record(float(v))
    assert h.samples == [0.0, 1.0, 2.0, 3.0]  # keep-first: deterministic
    assert (h.count, h.sum, h.max) == (10, 45.0, 9.0)  # stream stays exact
    assert h.percentile(99.0) == 3.0         # ...over the retained window


# ---------------------------------------------------------------------------
# Timeline edge cases (PR 10 satellite).
# ---------------------------------------------------------------------------

def test_timeline_idle_manager_renders_nothing():
    from repro.obs import timeline
    tm = Telemetry.create(clock=counting_clock())
    assert timeline.manager_tracks(tm.tracer, _mgr(telemetry=tm)) == 0
    assert tm.tracer.events == ()


def test_timeline_lossy_only_manager():
    """A manager whose only tenant is lossy still renders all three
    modeled lanes — fcfs, model, and the retry lane priced from the
    session's own ``level_counts``."""
    from repro.obs import timeline
    from repro.perfmodel import switch_model as sm
    plan = None
    counts = dataplane.level_packet_counts([4, 2], 3, 512, jnp.float32)
    for seed in range(200):
        cand = FaultPlan(seed=seed, drop=0.05, duplicate=0.2)
        if dataplane.plan_survives(cand, counts):
            plan = cand
            break
    assert plan is not None
    tm = Telemetry.create(clock=counting_clock())
    mgr = _mgr(telemetry=tm)
    mgr.open("lossy", mode="dense", num_buckets=3, bucket_elems=512,
             dtype=jnp.float32, fault_plan=plan)
    n = timeline.manager_tracks(tm.tracer, mgr)
    tracks = {e["track"] for e in tm.tracer.events}
    assert {"fcfs/lossy", "model/lossy", "lossy/lossy"} <= tracks, tracks
    lossy = [e for e in tm.tracer.events if e["track"] == "lossy/lossy"]
    assert n == 2 + len(lossy)
    # the lane prices the session's own level shapes via model_lossy
    sess = mgr.session("lossy")
    for ev, (p, npkt) in zip(lossy, [c for i, c in
                                     enumerate(sess.level_counts)
                                     if plan.applies(i)]):
        lp = sm.model_lossy(plan.drop, plan.corrupt, p * npkt,
                            max_retries=plan.retry.max_retries,
                            timeout_rounds=plan.retry.timeout_rounds,
                            backoff=plan.retry.backoff)
        assert ev["args"]["retransmits"] == lp.retransmits


def test_timeline_on_ring_truncated_tracer_still_exports(tmp_path):
    """A flight-recorder tracer (ring=N) keeps only the trailing window;
    the timeline renderer and the Chrome export must both survive the
    truncation (valid JSON, consistent lane metadata for the survivors)."""
    from repro.obs import timeline
    tm = Telemetry(registry=MetricsRegistry(),
                   tracer=Tracer(clock=counting_clock(), ring=3))
    mgr = _mgr(telemetry=tm)
    _open_two(mgr)                           # admission events overflow...
    n = timeline.manager_tracks(tm.tracer, mgr)
    assert n > 3                             # ...and so do modeled spans
    assert len(tm.tracer.events) == 3        # only the window survives
    doc = json.loads(tm.tracer.to_json(metrics=tm.registry.as_dict()))
    names = {e["name"] for e in doc["traceEvents"]}
    assert "thread_name" in names            # lane metadata re-derived
    kept = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert len(kept) == 3
    assert all(e["dur"] >= 0.0 for e in kept)


# ---------------------------------------------------------------------------
# Report CLI: histograms, incidents, --fail-on (PR 10 satellites).
# ---------------------------------------------------------------------------

def test_report_cli_renders_histogram_section(tmp_path, capsys):
    tm = Telemetry.create(clock=counting_clock())
    mgr = _mgr(telemetry=tm)
    _open_two(mgr)
    for v in (1.0, 2.0, 3.0, 100.0):
        tm.registry.histogram("step.dur_us").record(v)
    mpath = str(tmp_path / "m.json")
    tm.export_metrics(mpath)
    assert obs_report.main([mpath]) == 0
    out = capsys.readouterr().out
    assert "== histograms ==" in out
    assert "step.dur_us" in out
    assert "p95" in out and "100.0000" in out


def _incident_log(tmp_path, worst="warning"):
    from repro.obs import HealthMonitor
    tm = Telemetry.create(clock=counting_clock())
    tm.registry.counter("tenant.t.retransmits").inc(7)
    if worst == "critical":
        tm.registry.gauge("congestion.l1s0.hotness").set(1.5)
    hm = HealthMonitor(tm, clock=counting_clock())
    hm.poll()
    path = str(tmp_path / "incidents.json")
    hm.export_incidents(path)
    return path


def test_report_cli_renders_incident_log(tmp_path, capsys):
    path = _incident_log(tmp_path)
    assert obs_report.main(["--incidents", path]) == 0
    out = capsys.readouterr().out
    assert "== incidents ==" in out
    assert "[warning] fault_storm tenant=t:" in out
    assert "evidence: tenant.t.retransmits=7" in out


def test_report_cli_fail_on_gates_exit_code(tmp_path, capsys):
    path = _incident_log(tmp_path, worst="critical")
    # at/above the floor -> exit 1 with the count on stderr
    assert obs_report.main(["--incidents", path,
                            "--fail-on", "warning"]) == 1
    err = capsys.readouterr().err
    assert "FAIL:" in err and "warning" in err
    assert obs_report.main(["--incidents", path,
                            "--fail-on", "critical"]) == 1
    # floor above everything in the log -> clean exit
    calm = _incident_log(tmp_path)           # warning only
    assert obs_report.main(["--incidents", calm,
                            "--fail-on", "critical"]) == 0


def test_report_cli_argument_validation(tmp_path):
    with pytest.raises(SystemExit):
        obs_report.main([])                  # nothing to report
    with pytest.raises(SystemExit):          # --fail-on needs --incidents
        obs_report.main([str(tmp_path / "m.json"), "--fail-on", "warning"])
    with pytest.raises(SystemExit):          # unknown severity
        obs_report.main(["--incidents", "x.json", "--fail-on", "fatal"])


def test_report_cli_metrics_and_incidents_together(tmp_path, capsys):
    mpath, _tpath = _exported(tmp_path)
    ipath = _incident_log(tmp_path)
    assert obs_report.main([mpath, "--incidents", ipath]) == 0
    out = capsys.readouterr().out
    assert "== per-tenant ==" in out and "== incidents ==" in out


# ---------------------------------------------------------------------------
# Config neutrality.
# ---------------------------------------------------------------------------

def test_flare_config_telemetry_is_not_a_cache_key():
    bare = FlareConfig(axes=("data",))
    wired = FlareConfig(axes=("data",), telemetry=Telemetry.create())
    assert bare == wired
    assert hash(bare) == hash(wired)
    assert "telemetry" not in repr(wired)
