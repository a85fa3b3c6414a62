"""The numbers that decide ``correct``, each beside its limit.

A number passes when it is at most its limit.  Gaps of norms are taken leaf
by leaf, as the gap between the program's norm and the reference's over the
larger of the reference's norm of that leaf and of the median leaf, and the
worst leaf is reported.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, Iterable, List, Optional, Tuple


def loss_gap(program: List[float], reference: List[float]) -> float:
    """Largest relative gap of the steps' losses."""
    gaps = [abs(p - r) / abs(r) for p, r in zip(program, reference)]
    if len(program) != len(reference) or not all(map(math.isfinite, gaps)):
        return math.inf
    return max(gaps)


def negligible(reference_grad: Dict[str, float]) -> set:
    """Leaves whose reference gradient is under a thousandth of the median
    leaf's: under AdamW they move by round-off alone."""
    med = statistics.median(reference_grad.values())
    return {k for k, v in reference_grad.items() if v < 1e-3 * med}


def worst_norm_gap(program: Dict[str, float], reference: Dict[str, float],
                   skip: Iterable[str] = ()) -> Tuple[float, Optional[str]]:
    skip = set(skip)
    keys = [k for k in reference if k not in skip]
    if set(program) != set(reference):
        return math.inf, "leaf sets differ"
    med = statistics.median(reference[k] for k in keys)
    worst, name = -1.0, None
    for k in keys:
        gap = abs(program[k] - reference[k]) / max(reference[k], med)
        if not math.isfinite(gap):
            return math.inf, k
        if gap > worst:
            worst, name = gap, k
    return worst, name


def passed(checks: Dict[str, dict]) -> bool:
    """Every number at or under its limit; a number with no limit set
    cannot pass."""
    return all(c["limit"] is not None and c["value"] <= c["limit"]
               for c in checks.values())


def lines(checks: Dict[str, dict]) -> List[str]:
    return [f"check {name}: {c['value']!r} (limit {c['limit']!r})"
            + (f" worst leaf {c['leaf']}" if c.get("leaf") else "")
            for name, c in checks.items()]
