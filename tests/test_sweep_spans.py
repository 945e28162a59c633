"""The sweep's host spans, read back out of a profiler trace on the CPU.

A two-cell sweep (admission below 1, without and with an outage) over a
small fleet whose first pod's cache runs FIFO and whose second runs LRU,
so one query reaches all four kernels: stack distances, the FIFO replay,
the LRU state machine (an outage lets a cache serve before it has located
an object's size) and the waterfill.  The trace is read with
``ProfileData``, as the benchmark reads it.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import spans
from repro.core import FederationSpec, ScenarioSpec, SweepSpec, run_sweep
from repro.core.workload import AccessRequest

CHUNK = 24 * 2**20
KINDS = ("stack", "fifo", "cache_sim", "waterfill")
PER_CELL = (spans.CLASSIFY, spans.FINALIZE)


def sweep(n_requests: int) -> SweepSpec:
    """``n_requests`` one- and two-chunk reads of 24 files from two pods.
    Every stream stays under the kernels' smallest padded length, so the
    buckets, and with them the per-bucket spans, are the same at 40 and
    80 requests."""
    rng = np.random.default_rng(3)
    sizes = [int(CHUNK * 1.5) + 4096 * k for k in range(24)]
    files = rng.integers(0, len(sizes), size=n_requests)
    times = np.sort(rng.uniform(0.0, 600.0, size=n_requests))
    trace = [AccessRequest(time=float(times[i]), site=f"pod{i % 2}",
                           worker=i % 2, path=f"/data/f{k}", size=sizes[k],
                           experiment="x")
             for i, k in enumerate(files)]
    fed = FederationSpec.fleet(num_pods=2, hosts_per_pod=2)
    fed = dataclasses.replace(fed, sites=[
        dataclasses.replace(s, eviction_policy="fifo") if i == 0 else s
        for i, s in enumerate(fed.sites)])
    base = ScenarioSpec(name="spans", engine="analytic", federation=fed,
                        workload=trace)
    return SweepSpec(name="spans", base=base, axes={
        "federation.cache_capacity": [1e8],
        "federation.admission_max_fraction": [0.3],
        "outage_rate": [0.0, 0.5]})


def traced(n_requests, tmp_path):
    """The report of one traced sweep and its ``repro.`` host events as
    ``(name, start_ns, end_ns, stats)``."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    out = str(tmp_path / f"trace{n_requests}")
    with jax.profiler.trace(out, profiler_options=opts):
        report = run_sweep(sweep(n_requests))
    [path] = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                       recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                               dict(e.stats))
                              for e in line.events
                              if e.name.startswith("repro."))
    return report, events


def parents(events):
    """Each event's innermost enclosing event (``None`` for a root)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    stack, parent = [], {}
    for i in order:
        _, s, e, _ = events[i]
        while stack and not (events[stack[-1]][1] <= s
                             and e <= events[stack[-1]][2]):
            stack.pop()
        parent[i] = stack[-1] if stack else None
        stack.append(i)
    return parent


def self_ns(events, parent):
    """Each event's duration less what its direct children cover."""
    out = {i: e - s for i, (_, s, e, _) in enumerate(events)}
    for i, p in parent.items():
        if p is not None:
            out[p] -= events[i][2] - events[i][1]
    return out


def expected_names():
    names = {spans.SWEEP, spans.ROUTE, spans.ROUTE_STREAMS,
             spans.ROUTE_FLOWS, spans.CLASSIFY, spans.DISTANCES, spans.L2,
             spans.FINALIZE, spans.PRICE}
    for kind in KINDS:
        names.update(spans.kernel(kind))
    return names


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("spans")
    return {n: traced(n, tmp) for n in (40, 80)}


def test_every_span_appears_nested_under_the_sweep(runs):
    report, events = runs[40]
    solver = report.solver
    assert all(solver.get(f"{k}_calls", 0) >= 1
               for k in ("stack", "fifo", "cache_sim"))
    assert {name for name, *_ in events} == expected_names()
    roots = [ev for ev in events if ev[0] == spans.SWEEP]
    assert len(roots) == 1 and roots[0][3] == {"cells": 2}
    parent = parents(events)
    by_name = collections.defaultdict(list)
    for i, (name, *_rest) in enumerate(events):
        by_name[name].append(i)
        if name != spans.SWEEP:
            assert parent[i] is not None, name
    inner = {spans.ROUTE_STREAMS: spans.ROUTE,
             spans.ROUTE_FLOWS: spans.ROUTE,
             spans.kernel("stack")[0]: spans.DISTANCES}
    for kind in KINDS:
        call, *stages = spans.kernel(kind)
        inner.update({stage: call for stage in stages})
        assert all(events[i][3].get("problems", 0) >= 1
                   for i in by_name[call])
        assert all("bucket" in events[i][3]
                   for stage in stages for i in by_name[stage])
    for child, outer in inner.items():
        assert all(events[parent[i]][0] == outer for i in by_name[child]), \
            child
    for name in PER_CELL:
        assert sorted(events[i][3]["cell"] for i in by_name[name]) == [0, 1]


def test_span_count_does_not_grow_with_the_requests(runs):
    (r40, e40), (r80, e80) = runs[40], runs[80]
    assert r80.solver["stream_refs"] > r40.solver["stream_refs"]
    assert collections.Counter(name for name, *_ in e40) == \
        collections.Counter(name for name, *_ in e80)


@pytest.mark.parametrize("n", [40, 80])
def test_stage_spans_split_the_sweep_span_whole(runs, n):
    """The sweep's direct children are disjoint, and the stages the
    benchmark reports (route, finalize, kernel packing, what no stage
    splits, and device calls) add up to the sweep span."""
    _, events = runs[n]
    parent = parents(events)
    own = self_ns(events, parent)
    root = next(i for i, ev in enumerate(events) if ev[0] == spans.SWEEP)
    children = sorted((events[i][1], events[i][2])
                      for i, p in parent.items() if p == root)
    assert all(a[1] <= b[0] for a, b in zip(children, children[1:]))
    assert own[root] >= 0

    def total(names, self_time=False):
        return sum(own[i] if self_time else events[i][2] - events[i][1]
                   for i, ev in enumerate(events) if ev[0] in names)
    calls = {spans.kernel(k)[0] for k in KINDS}
    io = {name for k in KINDS for name in spans.kernel(k)[1::2]}
    device = {spans.kernel(k)[2] for k in KINDS}
    stages = (total({spans.ROUTE})
              + total({spans.FINALIZE})
              + total(io) + total(calls | {spans.DISTANCES, spans.PRICE},
                                  self_time=True)
              + own[root] + total({spans.CLASSIFY, spans.L2})
              + total(device))
    assert stages == pytest.approx(events[root][2] - events[root][1],
                                   rel=1e-12)


def test_answers_are_the_same_with_the_profiler_on_and_off(runs):
    traced_report, _ = runs[40]
    plain = run_sweep(sweep(40))
    assert [c.summary for c in plain.cells] == \
        [c.summary for c in traced_report.cells]
    assert [c.pricing for c in plain.cells] == \
        [c.pricing for c in traced_report.cells]
    assert plain.solver == traced_report.solver


@pytest.mark.parametrize("n", [40, 80])
def test_stream_refs_and_priced_flows_recount_from_the_report(runs, n):
    """Each cell here routes its own column (the outage changes the
    routing), and every reference of a column's streams is a hit or a
    miss of its one cell."""
    report, _ = runs[n]
    assert report.solver["stream_refs"] == sum(
        c.summary["cache_hits"] + c.summary["cache_misses"]
        for c in report.cells)
    assert report.solver["priced_flows"] == sum(
        c.pricing["peak_flows"] for c in report.cells)
