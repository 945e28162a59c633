"""Names of the host spans the analytic sweep writes into a profiler trace.

Each span is a ``jax.profiler.TraceAnnotation`` opened under its own name
at the call site (so that fedlint sees it): it lands on the
profiler's host plane, on the same clock as the device's events, so a
trace says what the host was doing in every stretch where the device sat
idle.  With no profiler running a span costs about half a microsecond.

Spans mark stage boundaries only, never an iteration over requests,
references or flows, so a query writes the same number of spans however
long its trace is.  None sits inside a function that JAX traces: there it
would run once, at trace time (fedlint's ``jit-purity`` rule flags it).
Keyword metadata (``cells``, ``cell``, ``problems``, ``bucket``) goes into
the event's stats and leaves its name as it is.
"""
from __future__ import annotations

from typing import Tuple

SWEEP = "repro.sweep"
"""All of ``run_sweep``; metadata ``cells``."""

ROUTE = "repro.sweep.route"
"""Building a shared federation, or routing one column (``_cell_routing``)."""

ROUTE_STREAMS = "repro.sweep.route.streams"
"""In a route: request arrays, liveness epochs, the per-cache streams."""

ROUTE_FLOWS = "repro.sweep.route.flows"
"""In a route: origin-direct seconds, serve/pull flow tables, counters."""

CLASSIFY = "repro.sweep.classify"
"""One cell's ``_CellPlan`` and its fit variants; metadata ``cell``."""

DISTANCES = "repro.sweep.distances"
"""``_resolve_distances``: ``prev`` chains, the kernel, end residency."""

L2 = "repro.sweep.l2"
"""The round-2 ``prepare_l2`` pass over every cell."""

FINALIZE = "repro.sweep.finalize"
"""One cell's counters, results, summary and fits; metadata ``cell``."""

PRICE = "repro.sweep.price"
"""One cell's flow problem, or the pricing read off the solved rates."""

KERNEL = "repro.kernel.{kind}"
"""One ``*_batch`` call (``kind``: a ``_tally`` kind or ``waterfill``);
metadata ``problems``.  Per bucket (metadata ``bucket``) it holds
``.pack`` (filling the padded arrays), ``.device`` (the jitted call
through its copy back to the host) and ``.unpack`` (slicing out each
problem's answer)."""


def kernel(kind: str) -> Tuple[str, str, str, str]:
    """The names of one kernel's call span and of its per-bucket pack,
    device and unpack spans."""
    name = KERNEL.format(kind=kind)
    return name, name + ".pack", name + ".device", name + ".unpack"
