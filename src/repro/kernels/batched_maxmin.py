"""Batched max-min waterfilling across heterogeneous problems (vmap).

The sweep engine (:func:`repro.core.api.run_sweep`) prices link
contention for *hundreds* of scenarios per solve: every sweep cell
contributes one (flows, links) max-min problem — its storm-counterfactual
flow set — and all cells are solved together.  Calling
``maxmin_rates_sparse`` per cell would pay one JIT dispatch (and, for
each new shape, one compile) per scenario; this module instead

* pads each problem to a power-of-two ``(Fp, Lp, width)`` bucket with the
  same dummy-link layout as :func:`repro.kernels.maxmin.pad_problem`,
* groups same-bucket problems into a ``(B, ...)`` stack (B itself padded
  to a power of two with all-dummy problems), and
* runs one ``jax.jit(jax.vmap(solve_waterfill))`` call per bucket.

Because the waterfilling ``while_loop`` body is idempotent once a
problem's ``active`` mask empties, vmap's run-until-all-done semantics
leave early-converging problems untouched while stragglers finish —
heterogeneous (flows, links) shapes cost only their bucket's padding.
The JIT cache therefore sees O(log² ) distinct shapes, not one per cell,
and a 200-cell sweep column is priced by a handful of device calls
(``stats["solve_calls"]``), which is what the sweep benchmark and the CI
regression gate assert.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from .. import spans
from .maxmin import _next_pow2, pad_problem, solve_waterfill

# One problem: (link_caps, flow_links, flow_caps) in the same layout as
# maxmin_rates_sparse — per-flow rows of link indices, per-flow caps.
Problem = Tuple[Sequence[float], Sequence[Sequence[int]], Sequence[float]]

_solve_batch = jax.jit(jax.vmap(solve_waterfill))
_SPANS = spans.kernel("waterfill")


def _bucket_of(problem: Problem) -> Tuple[int, int, int]:
    link_caps, flow_links, _ = problem
    width = _next_pow2(max((len(ls) for ls in flow_links), default=1),
                       floor=4)
    return (_next_pow2(len(flow_links)),
            _next_pow2(len(link_caps) + 1),
            width)


def maxmin_rates_batch(problems: Sequence[Problem],
                       stats: Optional[Dict] = None) -> List[np.ndarray]:
    """Solve many independent max-min problems in few jitted calls.

    Returns one ``(F_i,)`` rate array per input problem, in input order
    — each equal (up to float association) to what
    ``maxmin_rates_sparse`` returns for that problem alone, including
    the loopback fixup: flows crossing no capacity-bearing link get
    their own cap, not the padding rows' zero.

    ``stats``, when given, is filled with telemetry: ``solve_calls``
    (jitted batch invocations), ``buckets`` (``(B, Fp, Lp, width)`` per
    call), ``problems`` and ``padded_problems`` (all-dummy batch
    filler).  The sweep report surfaces these so benches can assert
    "one call priced the whole column".
    """
    if stats is not None:
        stats.update(solve_calls=0, buckets=[], problems=len(problems),
                     padded_problems=0)
    call, pack, device, unpack = _SPANS
    with TraceAnnotation(call, problems=len(problems)):
        out: List[Optional[np.ndarray]] = [None] * len(problems)
        by_bucket: Dict[Tuple[int, int, int], List[int]] = {}
        for i, p in enumerate(problems):
            by_bucket.setdefault(_bucket_of(p), []).append(i)
        for (Fp, Lp, width), idxs in sorted(by_bucket.items()):
            B = _next_pow2(len(idxs), floor=1)
            bucket = f"{B}x{Fp}x{Lp}x{width}"
            with TraceAnnotation(pack, bucket=bucket):
                caps = np.full((B, Lp), np.inf, np.float32)
                ids = np.full((B, Fp, width), Lp - 1, np.int32)
                fcaps = np.zeros((B, Fp), np.float32)
                for bi, i in enumerate(idxs):
                    caps[bi], ids[bi], fcaps[bi] = pad_problem(
                        *problems[i], Fp=Fp, Lp=Lp, width=width)
            with TraceAnnotation(device, bucket=bucket):
                rates = np.asarray(_solve_batch(caps, ids, fcaps))
            if stats is not None:
                stats["solve_calls"] += 1
                stats["buckets"].append((B, Fp, Lp, width))
                stats["padded_problems"] += B - len(idxs)
            with TraceAnnotation(unpack, bucket=bucket):
                for bi, i in enumerate(idxs):
                    link_caps_i, flow_links_i, flow_caps_i = problems[i]
                    res = rates[bi, :len(flow_links_i)].astype(np.float64)
                    # Same loopback parity fixup as maxmin_rates_sparse:
                    # an all-dummy row is indistinguishable from padding
                    # inside the solve but is a real flow bound only by
                    # its own cap.
                    for fi, ls in enumerate(flow_links_i):
                        if not ls:
                            res[fi] = flow_caps_i[fi]
                    out[i] = res
        return [r if r is not None else np.zeros(0) for r in out]
