"""Names of the host spans the program writes into a profiler trace.

Each span is a ``jax.profiler.TraceAnnotation`` opened under its own name
at the call site (so that fedlint sees it): it lands on the
profiler's host plane, on the same clock as the device's events, so a
trace says what the host was doing in every stretch where the device sat
idle.  With no profiler running a span costs about half a microsecond.

Spans mark stage boundaries only, never an iteration over requests,
references or flows, so a sweep writes the same number of spans however
long its trace is.  One exception: the fluid-flow simulator writes one
``repro.sim.solve`` span, with the ``repro.kernel.maxmin`` spans of its
``maxmin_rates_sparse`` call inside, per vector solve.  There the unit of
device work is one solve (a max-min allocation re-solved once per event
time), and each solve is one module event on the device already; a span
set per solve names the host work around each of those events, and the
trace grows with the number of solves as it does anyway.  Scalar solves run
on the host alone and get no span.  None sits inside a function that JAX
traces: there it would run once, at trace time (fedlint's ``jit-purity``
rule flags it).  Keyword metadata (``cells``, ``cell``, ``problems``,
``bucket``, ``flows``) goes into the event's stats and leaves its name as
it is.
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
"""Every cell's ``prepare_l2``: the merged parent-tier streams, queued in
the executor's second ``_Round``."""

FINALIZE = "repro.sweep.finalize"
"""One cell's counters, results, summary and fits; metadata ``cell``."""

PRICE = "repro.sweep.price"
"""One cell's flow problem, or the pricing read off the solved rates."""

KERNEL = "repro.kernel.{kind}"
"""One ``*_batch`` call (``kind``: a ``_tally`` kind or ``waterfill``);
metadata ``problems``.  All four run the bucket loop of
``repro.kernels.batching.run_buckets``: per bucket (metadata ``bucket``)
it holds ``.pack`` (filling the padded arrays), ``.device`` (the jitted
call through its copy back to the host) and ``.unpack`` (slicing out
each problem's answer).  Kind ``maxmin`` is one ``maxmin_rates_sparse`` solve
(metadata ``flows``): ``.pack`` pads it to its bucket, ``.unpack`` is the
loopback fix-up."""

SCENARIO_BUILD = "repro.scenario.build"
"""``run_scenario``'s federation build, request list and publishing."""

SCENARIO_REPORT = "repro.scenario.report"
"""``run_scenario``'s report: ``_report`` and the per-scenario deltas."""

SIM = "repro.sim"
"""``FluidFlowSim.run``'s event loop: coroutines, event heaps, scalar
solves, finish-heap rebuilds."""

SIM_SOLVE = "repro.sim.solve"
"""One vector solve (``_reallocate_vector``; metadata ``flows``): the
flow problem's index build and the rate write-back are its own time;
inside it, the ``repro.kernel.maxmin`` spans of the solve."""


def kernel(kind: str) -> Tuple[str, str, str, str]:
    """The names of one kernel's call span and of its per-bucket pack,
    device and unpack spans."""
    name = KERNEL.format(kind=kind)
    return name, name + ".pack", name + ".device", name + ".unpack"
