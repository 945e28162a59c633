"""The program's own host spans in a traced run, beside the device events.

The program names its stages with ``jax.profiler.TraceAnnotation`` spans
whose names start with ``repro.`` (``repro.sweep`` around a whole sweep,
stage and kernel spans inside it).  :func:`program_spans` reads them out
of the profiler's file; added to the host list that ``bench.trace.load``
gives, they make ``bench.trace.reduce`` name each idle gap by the
innermost program span around it, and everything ``reduce`` read before
reads the same.  :func:`reduce` adds three keys:

* ``span_s``: seconds per span name, summed over the window;
* ``span_self_s``: per span name, the spans' seconds less what the
  ``repro.`` spans nested in them cover;
* ``idle_unattributed_s``: device-idle seconds in the window that no
  ``repro.`` span other than the root ``repro.sweep`` covers.

:func:`of_run` finds a finished run's trace on disk for the metric
readers.  A program that writes no ``repro.sweep`` span gives ``None``
there, and its readers report nothing.
"""
from __future__ import annotations

import glob
import json
import os
from pathlib import Path
from typing import Dict, List, Optional

from bench import trace

PREFIX = "repro."
ROOT_SPAN = "repro.sweep"
# where run.py leaves each cell's trace (``OUT / "trace" / <cell>``)
TRACE_ROOT = Path(__file__).resolve().parents[1] / "out" / "trace"


def program_spans(trace_dir: str) -> List[trace.Event]:
    """The ``repro.`` host spans of the trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one trace file under {trace_dir}, "
                         f"found {len(paths)}")
    out: List[trace.Event] = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, float(e.start_ns), float(e.duration_ns))
                           for e in line.events
                           if e.name.startswith(PREFIX))
    return out


def _self_ns(spans: List[trace.Event]) -> List[float]:
    """Each span's duration less its direct children's (spans nest: a
    child lies inside its parent, siblings do not overlap)."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][1], -spans[i][2]))
    own = [d for _, _, d in spans]
    stack: List[int] = []
    for i in order:
        _, s, d = spans[i]
        while stack and not (s + d <= spans[stack[-1]][1]
                             + spans[stack[-1]][2]):
            stack.pop()
        if stack:
            own[stack[-1]] -= d
        stack.append(i)
    return own


def reduce(events: Dict, top: int = 10) -> Optional[Dict]:
    """``bench.trace.reduce`` of ``events`` with ``span_s``,
    ``span_self_s`` and ``idle_unattributed_s``; ``None`` where the
    trace holds no query, no device or no ``repro.sweep`` span in the
    window."""
    out = trace.reduce(events, top)
    if out is None:
        return None
    lo, hi = trace.window_ns(events)
    spans = [(n, max(s, lo), min(s + d, hi) - max(s, lo))
             for n, s, d in events["host"]
             if n.startswith(PREFIX) and s + d > lo and s < hi]
    if not any(n == ROOT_SPAN for n, _, _ in spans):
        return None
    span_s: Dict[str, float] = {}
    self_s: Dict[str, float] = {}
    for (name, _, d), own in zip(spans, _self_ns(spans)):
        span_s[name] = span_s.get(name, 0.0) + d * 1e-9
        self_s[name] = self_s.get(name, 0.0) + own * 1e-9
    named = trace._union((s, s + d) for n, s, d in spans if n != ROOT_SPAN)
    unattributed = 0.0
    for dev in events["devices"].values():
        busy = trace._union(trace._clip(dev["modules"], lo, hi))
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                unattributed += (b - a) - sum(
                    max(0.0, min(b, y) - max(a, x)) for x, y in named)
    out["span_s"] = span_s
    out["span_self_s"] = self_s
    out["idle_unattributed_s"] = unattributed / len(events["devices"]) * 1e-9
    return out


def of_run(run) -> Optional[Dict]:
    """:func:`reduce` of the trace that a finished traced run left on
    disk: the cell directory under ``out/trace`` whose recorded events
    reduce to ``run.trace``, with the program's spans added."""
    if run.trace is None:
        return None
    for path in sorted(TRACE_ROOT.glob("*/events.json"),
                       key=lambda p: -p.stat().st_mtime):
        events = json.loads(path.read_text())
        if trace.reduce(events) != run.trace:
            continue
        events["host"].extend(program_spans(str(path.parent)))
        return reduce(events)
    return None


def per_query(run, seconds) -> Optional[float]:
    """``seconds(reduced)`` over the traced queries, or ``None`` where
    :func:`of_run` finds no program spans."""
    reduced = of_run(run)
    if reduced is None or not reduced["queries"]:
        return None
    return seconds(reduced) / reduced["queries"]
