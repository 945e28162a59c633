"""The comparison that decides ``correct`` for a sweep.

Every cell of every query the run answered (the warm-up's and the
window's) is held against the plain reference's answer for that cell.

* Counters: the deployment guarantees exact byte accounting, so each is
  compared exactly: the number is the widest absolute gap over all
  answers, and its limit is 0.  So is the storm's flow count.
* Pricing: the storm's finish, its least and its mean rate, each by the
  widest gap relative to the reference's value.  The program solves in
  float32 and the reference in float64; the limits lie between what
  sound runs read and what the bfloat16 control reads (``PERF.md``).
* The sweep must also have answered every cell on the batched path.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

from bench.pricing import EXACT, RELATIVE
from bench.reference import COUNTERS

LIMITS = {f"gap.{c}": 0.0 for c in COUNTERS + EXACT}
LIMITS.update({"rel.storm_finish_seconds": 1e-4, "rel.min_rate": 1e-4,
               "rel.mean_rate": 1e-4})
LIMITS["serial_cells"] = 0.0


def _worst(a: float, b: float) -> float:
    """The larger gap, where a NaN (a number not reported) beats all."""
    return a if a != a or not (b != b or b > a) else b


def compare(reference: Sequence[Dict[str, float]],
            queries: Sequence[List[Dict[str, float]]],
            serial_cells: int) -> Dict[str, Dict[str, float]]:
    """``{name: {"value", "limit"}}`` for every number compared."""
    gaps = {c: 0.0 for c in COUNTERS + EXACT}
    rel = {c: 0.0 for c in RELATIVE}
    for answers in queries:
        if len(answers) != len(reference):
            raise ValueError(f"a query answered {len(answers)} cells, "
                             f"the grid has {len(reference)}")
        for got, want in zip(answers, reference):
            for c in gaps:
                gaps[c] = _worst(gaps[c], abs(got.get(c, float("nan"))
                                              - want.get(c, 0.0)))
            for c in rel:
                w = want.get(c, 0.0)
                gap = abs(got.get(c, float("nan")) - w)
                rel[c] = _worst(rel[c], gap / abs(w) if w else gap)
    out = {f"gap.{c}": {"value": v, "limit": LIMITS[f"gap.{c}"]}
           for c, v in gaps.items()}
    out.update({f"rel.{c}": {"value": v, "limit": LIMITS[f"rel.{c}"]}
                for c, v in rel.items()})
    out["serial_cells"] = {"value": float(serial_cells),
                           "limit": LIMITS["serial_cells"]}
    return out


def correct(checks: Dict[str, Dict[str, float]]) -> bool:
    # a NaN gap (a number the program did not report) is not <= its limit
    return all(v["value"] <= v["limit"] for v in checks.values())
