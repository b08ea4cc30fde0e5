"""Which layer of the program issued each device op: the program's
``jax.named_scope`` paths, joined with a profiler trace by op name.

The program names its layers with ``jax.named_scope``: ``flare.*`` the
gradient reducer, its transports and FSDP, ``plane.*`` the in-network
data plane, ``mamba2.*`` and ``lm.*`` the model, ``train.*`` the
optimizer step.  A scope adds no op.  It becomes the ``op_name`` in the
metadata of each HLO instruction made under it, e.g.
``jit(step_body)/transpose(jvp(mamba2.ssd))/while/body/dot_general``,
and stays there through the compiler's passes.  A fusion takes its
root's metadata (the compiler copies it onto the fusion).  An op the
compiler made without any takes a neighbour's: the computation it
calls, its first operand, its loop, or its first user (``op_scopes``).

The trace's device ops carry the instruction's name alone
(``fusion.962``, see ``bench/trace.py``), so the join is on that name,
in the text of the optimized module (``compiled.as_text()``).  A path
matches a scope where one of its components, with the transform
wrappers JAX puts round it (``jvp(…)``, ``transpose(…)``, ``remat(…)``,
``vmap(…)``) taken off, is the scope: the backward pass counts with its
layer, and ``while/body`` is only more components.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import re

from bench import trace as trace_mod

#: the prefixes of the program's own scopes; an op under none of them is
#: unscoped
PREFIXES = ("flare.", "plane.", "mamba2.", "lm.", "train.")

_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")
_INSTR = re.compile(r"^\s*(ROOT\s+)?%([^\s=]+) = (.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?\bop_name="((?:[^"\\]|\\.)*)"')
_CALLED = re.compile(r"\b(?:calls|body|condition|to_apply)=%([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")
_WRAPPER = re.compile(r"^[\w.\-]+\((.*)\)$")


@dataclasses.dataclass(frozen=True)
class OpScopes:
    """One compiled module: its name and, per instruction name, the
    ``op_name`` path it was made under."""

    module: str
    paths: dict[str, str]

    @property
    def scoped(self) -> bool:
        """Whether any op carries one of the program's scopes."""
        return any(_under_any(p) for p in self.paths.values())


def op_scopes(hlo_text: str) -> OpScopes:
    """The ``op_name`` path of each instruction of an optimized HLO
    module's text.

    Where the compiler merged ops, the metadata lists each one's path;
    the first under one of the program's scopes is taken, else the
    first.  An instruction the compiler made without metadata takes, in
    this order: the path of the computation it calls (a fusion's or a
    loop's: that computation's root's, or else its last own path); its
    first operand's (a copy, a convert, a rewritten reduce-window
    belongs to the layer whose data it handles); the path of the
    instruction that calls its computation (an op made in a loop body
    belongs to the loop); its first user's (a buffer of zeros belongs to
    the op that fills it)."""
    module = ""
    comps: list[tuple[str, list]] = []   # in text order: callees first
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            if not module and (mm := _MODULE.match(line)):
                module = mm.group(1)
            elif line.rstrip().endswith("{") and " = " not in line:
                name = line.split()[1 if line.startswith("ENTRY") else 0]
                comps.append((name.lstrip("%"), []))
            continue
        root, name, rest = m.groups()
        op = _OP_NAME.search(rest)
        own = None
        if op:
            merged = op.group(1).split(";")
            own = next((p for p in merged if _under_any(p)), merged[0])
        body = rest.split(", metadata=")[0]
        refs = _REF.findall(body)
        # a tuple gathers unrelated values; its users say what it is for
        first = refs[:1] if " tuple(" not in body else []
        comps[-1][1].append((name, bool(root), own, _CALLED.findall(body),
                             first, refs))
    paths: dict[str, str] = {}
    comp_path: dict[str, str] = {}       # computation → its root's path
    caller: dict[str, str] = {}          # computation → an instruction
    users: dict[str, str] = {}           # instruction → its first user
    for comp, instrs in comps:
        for name, root, own, called, first, refs in instrs:
            for c in called:
                caller.setdefault(c, name)
            for r in refs:
                users.setdefault(r, name)
            path = own or next((comp_path[c] for c in called
                                if c in comp_path), None) or next(
                (paths[r] for r in first if r in paths), None)
            if path is None:
                continue
            paths[name] = path
            if root or own:
                comp_path[comp] = path
    changed = True
    while changed:              # a loop found by its users names its body
        changed = False
        for comp, instrs in reversed(comps):      # callers and users first
            outer = paths.get(caller.get(comp, ""))
            for name, *_ in reversed(instrs):
                path = outer if outer is not None else paths.get(
                    users.get(name, ""))
                if name not in paths and path is not None:
                    paths[name] = path
                    changed = True
    return OpScopes(module, paths)


@functools.lru_cache(maxsize=4096)
def components(path: str) -> tuple[str, ...]:
    """``a/jvp(b)/transpose(jvp(c))/d`` → ``(a, b, c, d)``."""
    out = []
    for c in path.split("/"):
        while (m := _WRAPPER.match(c)) is not None:
            c = m.group(1)
        out.append(c)
    return tuple(out)


def under(path: str | None, scope: str) -> bool:
    """Whether ``path`` is under ``scope``: a component equal to it, or
    for a scope ending in ``.`` (a prefix), a component starting so."""
    if path is None:
        return False
    if scope.endswith("."):
        return any(c.startswith(scope) for c in components(path))
    return scope in components(path)


def _under_any(path: str | None) -> bool:
    return any(under(path, p) for p in PREFIXES)


def op_seconds(trace) -> dict[str, float]:
    """Self time in the window of each non-async op, by op name, per
    chip, averaged over the chips (as ``trace.self_seconds`` counts)."""
    out: dict[str, float] = {}
    for dev in trace.devices:
        for o in trace.ops(dev):
            if not o.is_async:
                out[o.name] = out.get(o.name, 0.0) + \
                    trace_mod._self_in_window(trace, o)
    return {k: v * 1e-9 / len(trace.devices) for k, v in out.items()}


def _seconds(times: dict[str, float], op_scopes: OpScopes, keep) -> float:
    return sum(t for name, t in times.items()
               if keep(op_scopes.paths.get(name)))


def _unscoped(times: dict[str, float], op_scopes: OpScopes) -> float | None:
    total = sum(times.values())
    if total <= 0:
        return None
    return 100.0 * _seconds(times, op_scopes,
                            lambda p: not _under_any(p)) / total


def scope_seconds(trace, op_scopes: OpScopes, scope: str) -> float:
    """Self time of the ops under ``scope`` (a name, or a prefix ending
    in ``.``), per chip, averaged over the chips."""
    return _seconds(op_seconds(trace), op_scopes,
                    lambda p: under(p, scope))


def unscoped_share(trace, op_scopes: OpScopes) -> float | None:
    """Share, in percent, of the ops' self time that falls under none of
    the program's scopes (``PREFIXES``); an op name the module does not
    hold counts as unscoped."""
    return _unscoped(op_seconds(trace), op_scopes)


def _live_programs() -> list:
    import jax
    return [o for o in gc.get_objects() if isinstance(o, jax.stages.Compiled)]


def _run_times(ctx: dict) -> dict[str, float]:
    if "op_seconds" not in ctx:
        ctx["op_seconds"] = op_seconds(ctx["trace"])
    return ctx["op_seconds"]


def of_run(ctx: dict) -> OpScopes | None:
    """The op scopes of the program that ran in the traced window, or
    ``None`` where there is no trace or the program carries none of its
    scopes.

    ``ctx["op_scopes"]`` is used where the harness gives it.  Otherwise
    the compiled programs alive in this process are read: the object
    that runs the cell still holds its executable while the readers run.
    The one whose instruction names cover most of the window's op time,
    and at least half of it, is taken.  It and the op times are kept in
    ``ctx`` for the next reader."""
    tr = ctx.get("trace")
    if tr is None or not tr.devices:
        return None
    if "op_scopes" not in ctx:
        times = _run_times(ctx)
        total = sum(times.values())
        found = [(_seconds(times, s, lambda p: p is not None), s) for s in
                 (op_scopes(c.as_text()) for c in _live_programs())]
        best = max(found, key=lambda cs: cs[0], default=(0.0, None))
        ctx["op_scopes"] = best[1] if total > 0 and best[0] >= total / 2 \
            else None
    s = ctx["op_scopes"]
    return s if s is not None and s.scoped else None


def ms_per(ctx: dict, scopes: tuple[str, ...], count: str) -> float | None:
    """Milliseconds of op self time under any of ``scopes`` per
    ``ctx["counts"][count]`` (calls or steps); ``None`` where the run
    has no scoped program or no such count.  The scopes must not nest."""
    s, n = of_run(ctx), ctx["counts"].get(count)
    if s is None or not n:
        return None
    times = _run_times(ctx)
    return 1e3 * sum(_seconds(times, s, lambda p, x=x: under(p, x))
                     for x in scopes) / n


def unscoped_of_run(ctx: dict) -> float | None:
    """``unscoped_share`` of the traced window's program, or ``None``
    where the run has no scoped program."""
    s = of_run(ctx)
    return None if s is None else _unscoped(_run_times(ctx), s)
