"""The comparisons that decide ``correct``, and their printout.

Each number compared has a limit of its own (``bench/limits/<cell>.json``,
with the readings it was set from).  A check is ``{"value", "limit"}``;
the run is correct when every value is finite and at most its limit.
"""
from __future__ import annotations

import math
import statistics


def norm_gap(prog: list[float], ref: list[float],
             keep: list[bool] | None = None) -> float:
    """Worst leaf's |‖prog‖ − ‖ref‖|, over the larger of the reference
    leaf's norm and the median leaf's."""
    med = statistics.median(ref)
    keep = keep or [True] * len(ref)
    gaps = [abs(p - r) / max(r, med)
            for p, r, k in zip(prog, ref, keep, strict=True) if k]
    return max(gaps)


def moved(ref_grad_norms: list[float], frac: float = 1e-3) -> list[bool]:
    """Leaves whose first reference gradient is above ``frac`` of the
    median leaf's: the others move under Adam by round-off alone and are
    left out of the parameter-change comparison."""
    med = statistics.median(ref_grad_norms)
    return [g > frac * med for g in ref_grad_norms]


def checks(values: dict[str, float], limits: dict[str, float]) -> dict:
    return {k: {"value": values[k], "limit": limits[k]} for k in limits}


def passed(chk: dict) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in chk.values())


def render(chk: dict) -> list[str]:
    return [f"check {k}: {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if math.isfinite(c['value']) and c['value'] <= c['limit'] else 'FAIL'}"
            for k, c in chk.items()]
