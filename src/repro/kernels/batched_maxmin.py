"""Batched max-min waterfilling across heterogeneous problems (vmap).

The sweep engine (:func:`repro.core.api.run_sweep`) prices link
contention for *hundreds* of scenarios per solve: every sweep cell
contributes one (flows, links) max-min problem — its storm-counterfactual
flow set — and all cells are solved together.  Calling
``maxmin_rates_sparse`` per cell would pay one JIT dispatch (and, for
each new shape, one compile) per scenario; this module instead pads each
problem to a power-of-two ``(Fp, Lp, width)`` bucket with the same
dummy-link layout as :func:`repro.kernels.maxmin.pad_problem` and runs
one ``jax.jit(jax.vmap(solve_waterfill))`` call per bucket through
:func:`repro.kernels.batching.run_buckets` (which pads the batch ``B``
to a power of two with all-dummy problems).

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

from .. import spans
from .batching import _next_pow2, run_buckets
from .maxmin import pad_problem, solve_waterfill

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


def _pack(members: Sequence[Problem], B: int,
          key: Tuple[int, int, int]) -> Tuple[np.ndarray, ...]:
    Fp, Lp, width = key
    caps = np.full((B, Lp), np.inf, np.float32)
    ids = np.full((B, Fp, width), Lp - 1, np.int32)
    fcaps = np.zeros((B, Fp), np.float32)
    for bi, problem in enumerate(members):
        caps[bi], ids[bi], fcaps[bi] = pad_problem(
            *problem, Fp=Fp, Lp=Lp, width=width)
    return caps, ids, fcaps


def _unpack(rates: np.ndarray, row: int, problem: Problem) -> np.ndarray:
    _link_caps, flow_links, flow_caps = problem
    res = rates[row, :len(flow_links)].astype(np.float64)
    # Same loopback parity fixup as maxmin_rates_sparse: an all-dummy
    # row is indistinguishable from padding inside the solve but is a
    # real flow bound only by its own cap.
    for fi, ls in enumerate(flow_links):
        if not ls:
            res[fi] = flow_caps[fi]
    return res


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
    return run_buckets(_SPANS, problems, _bucket_of, _pack, _solve_batch,
                       _unpack, stats)
