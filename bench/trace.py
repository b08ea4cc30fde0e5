"""From a JAX profiler trace to device busy time, op times and idle gaps.

A traced run writes an XSpace (``*.xplane.pb``).  Each TPU is one plane
named ``/device:TPU:<n>``.  Its ``XLA Ops`` line holds one event per HLO
op that ran, named by the op's HLO text (``%fusion.12 = ...``), with its
start and duration in nanoseconds on the same clock as the host planes;
a loop's op (``while``) spans the ops of its body, which nest inside it.
Its ``Async XLA Ops`` line spans each asynchronous op (a copy, a
collective) from its start to its done.  The harness's own host spans
(``jax.profiler.TraceAnnotation``) sit on the host plane's thread lines.

Everything here is plain arithmetic on those events, so a recorded
trace replays it exactly (``tests/bench``).
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import os
import re

#: HLO ops that move data between chips.  Async pairs (``-start`` /
#: ``-done``) match by their prefix.
COLLECTIVE_RE = re.compile(
    r"^(collective-permute|all-gather|reduce-scatter|all-reduce|"
    r"all-to-all|ragged-all-to-all|send|recv)")

#: the lines of a device plane: ops run one at a time, and async ops
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"

#: host spans the harness opens; an idle gap is named by the innermost
#: one of these that covers it
SPAN_PREFIX = "bench."


@dataclasses.dataclass(frozen=True)
class Op:
    name: str              # the HLO op's name, e.g. ``fusion.12``
    start_ns: float
    dur_ns: float
    self_ns: float = 0.0   # duration less the ops nested in it
    leaf: bool = True      # no op nested in it
    is_async: bool = False

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def collective(self) -> bool:
        return bool(COLLECTIVE_RE.match(self.name))


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Trace:
    """Device ops per chip and the harness's host spans, one window."""

    devices: dict[str, list[Op]]
    spans: list[Span]
    t0_ns: float          # the window, on the trace's clock
    t1_ns: float

    @property
    def window_s(self) -> float:
        return (self.t1_ns - self.t0_ns) * 1e-9

    def ops(self, device: str) -> list[Op]:
        """The device's ops that overlap the window."""
        return [o for o in self.devices[device]
                if o.end_ns > self.t0_ns and o.start_ns < self.t1_ns]


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` → ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def nest(events: list[tuple[str, float, float]]) -> list[Op]:
    """Ops of one line, each with its self time and whether it is a leaf
    (ops nested inside a loop's op are its children)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    self_ns = [d for _, _, d in evs]
    leaf = [True] * len(evs)
    stack: list[int] = []
    for i, (_, start, dur) in enumerate(evs):
        while stack and evs[stack[-1]][1] + evs[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            p = stack[-1]
            if start + dur <= evs[p][1] + evs[p][2]:
                self_ns[p] -= dur
                leaf[p] = False
        stack.append(i)
    return [Op(n, s, d, self_ns[i], leaf[i])
            for i, (n, s, d) in enumerate(evs)]


def load(path: str, window_span: str = "bench.window") -> Trace:
    """Read one ``.xplane.pb`` (or ``.xplane.pb.gz``); the window is the
    host span so named."""
    from jax.profiler import ProfileData

    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    devices: dict[str, list[Op]] = {}
    spans: list[Span] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops: list[Op] = []
            for line in plane.lines:
                evs = [(op_name(e.name), e.start_ns, e.duration_ns)
                       for e in line.events]
                if line.name == OPS_LINE:
                    ops += nest(evs)
                elif line.name == ASYNC_LINE:
                    ops += [Op(n, s, d, 0.0, False, True) for n, s, d in evs]
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Span(e.name, e.start_ns,
                                          e.start_ns + e.duration_ns))
    windows = [s for s in spans if s.name == window_span]
    if not windows:
        raise ValueError(f"{path}: no host span {window_span!r}")
    win = windows[-1]
    return Trace(devices, spans, win.start_ns, win.end_ns)


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise ValueError(f"{log_dir}: {len(paths)} xplane files, want 1")
    return paths[0]


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(trace: Trace, ops: list[Op]) -> list[tuple[float, float]]:
    return [(max(o.start_ns, trace.t0_ns), min(o.end_ns, trace.t1_ns))
            for o in ops]


def _leaves(trace: Trace, dev: str) -> list[Op]:
    return [o for o in trace.ops(dev) if o.leaf and not o.is_async]


def busy_s(trace: Trace) -> float:
    """Seconds in which an op (not a loop around ops) ran, per chip,
    averaged over the chips."""
    if not trace.devices:
        return 0.0
    tot = 0.0
    for dev in trace.devices:
        tot += sum(b - a for a, b in union(_clip(trace, _leaves(trace, dev))))
    return tot * 1e-9 / len(trace.devices)


def idle_percent(trace: Trace | None) -> float | None:
    """Share of the window in which no op ran, in percent (``None``
    without a trace or a device)."""
    if trace is None or not trace.devices or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - busy_s(trace) / trace.window_s)


def _self_in_window(trace: Trace, o: Op) -> float:
    """The op's self time, scaled to the part of it inside the window."""
    a, b = max(o.start_ns, trace.t0_ns), min(o.end_ns, trace.t1_ns)
    return o.self_ns * (b - a) / o.dur_ns if o.dur_ns > 0 else 0.0


def self_seconds(trace: Trace, *, collective: bool) -> float:
    """Self time of the (non-)collective ops that run one at a time, per
    chip, averaged over the chips."""
    if not trace.devices:
        return 0.0
    tot = sum(_self_in_window(trace, o) for dev in trace.devices
              for o in trace.ops(dev)
              if not o.is_async and o.collective == collective)
    return tot * 1e-9 / len(trace.devices)


def collective_seconds(trace: Trace) -> float:
    """Seconds in which a collective was issued, in flight or waited
    for, per chip, averaged over the chips."""
    if not trace.devices:
        return 0.0
    tot = 0.0
    for dev in trace.devices:
        coll = [o for o in trace.ops(dev)
                if o.collective and (o.leaf or o.is_async)]
        tot += sum(b - a for a, b in union(_clip(trace, coll)))
    return tot * 1e-9 / len(trace.devices)


def _base_name(name: str) -> str:
    """``fusion.123`` → ``fusion``: ops of one kind under one name."""
    return re.sub(r"\.\d+$", "", name)


def top_ops(trace: Trace, k: int = 10) -> list[list]:
    """The ``k`` op kinds with the most self time (seconds per chip)."""
    tot: dict[str, float] = {}
    n = max(len(trace.devices), 1)
    for dev in trace.devices:
        for o in trace.ops(dev):
            if o.is_async:
                continue
            key = _base_name(o.name)
            tot[key] = tot.get(key, 0.0) + _self_in_window(trace, o) * 1e-9 / n
    return [[name, s] for name, s in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(trace: Trace, k: int = 10) -> list[list]:
    """The ``k`` longest idle gaps of the first chip, each named by the
    innermost harness span open at its midpoint (``idle`` if none)."""
    if not trace.devices:
        return []
    dev = sorted(trace.devices)[0]
    busy = union(_clip(trace, _leaves(trace, dev)))
    edges = [trace.t0_ns] + [x for ab in busy for x in ab] + [trace.t1_ns]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, b in gaps[:k]:
        mid = (a + b) / 2
        cover = [s for s in trace.spans if s.start_ns <= mid <= s.end_ns
                 and s.name != "bench.window"]
        name = min(cover, key=lambda s: s.end_ns - s.start_ns).name \
            if cover else "idle"
        out.append([name, (b - a) * 1e-9])
    return out
