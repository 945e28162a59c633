"""Query kind ``sweep``: a what-if grid through the program's
``run_sweep``.

A kind is the one module that drives the program for one sort of query
(``traffic/<mix>.json`` names it under ``kind``); the harness finds it as
``kinds/<kind>.py``.  It exposes ``SPANS`` (the program's entry points
that a traced run wraps in host spans), ``spans(log)`` and ``Query``:
built from the configuration, the mix and ``--seed`` in set-up, run
again and again in the window, and checked against the plain reference
once the window has closed.

Here a query is one ``SweepSpec`` over the configuration's trace, run
with ``batched=True``; an answer is each cell's counters and contention
pricing under the reference's names.  Which sweep axes the reference
models, and under what name, is the configuration's ``sweep_axes``.
"""
from __future__ import annotations

import contextlib
import importlib
import time
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Tuple

from bench import checks, pricing, reference, traffic

# run_sweep imports these wrappers from their modules at call time, so
# wrapping the module attribute wraps the call.
SPANS = (
    ("repro.kernels.stack_distance", "stack_distances_batch"),
    ("repro.kernels.stack_distance", "fifo_sim_batch"),
    ("repro.kernels.stack_distance", "cache_sim_batch"),
    ("repro.kernels.batched_maxmin", "maxmin_rates_batch"),
)


def federation_spec(deployment: Dict):
    from repro.core import FederationSpec
    fed = deployment["federation"]
    spec = getattr(FederationSpec, fed["preset"])(**fed.get("args", {}))
    names = list(spec.cache_names())
    if names != list(deployment["caches"]):
        raise ValueError(f"the program's caches {names} are not the "
                         f"configuration's {deployment['caches']}")
    return spec


def sweep_spec(deployment: Dict, requests: Sequence[Dict],
               axes: Dict[str, List]):
    from repro.core import ScenarioSpec, SweepSpec
    from repro.core.workload import AccessRequest
    unknown = set(axes) - set(reference_axes(deployment))
    if unknown:
        raise ValueError(f"axes {sorted(unknown)} have no reference")
    trace = [AccessRequest(time=r["time"], site=r["site"],
                           worker=r["worker"], path=r["path"],
                           size=r["size"], experiment=r["experiment"])
             for r in requests]
    base = ScenarioSpec(name="chipbench", engine="analytic",
                        federation=federation_spec(deployment),
                        workload=trace)
    return SweepSpec(name="chipbench", base=base, axes=axes)


def reference_axes(deployment: Dict) -> Dict[str, Tuple[str, object]]:
    return {k: tuple(v) for k, v in deployment["sweep_axes"].items()
            if k != "about"}


def reference_cell(deployment: Dict, params: Dict[str, object]
                   ) -> Dict[str, object]:
    """A grid cell's parameters under the reference's names."""
    cell = {}
    for axis, (name, default) in reference_axes(deployment).items():
        value = params.get(axis, default)
        if value is None:
            raise ValueError(f"the query must give the axis {axis}")
        cell[name] = value
    return cell


def answers(report) -> List[Dict[str, float]]:
    """Each cell's counters and pricing, under the reference's names."""
    out = []
    for cell in report.cells:
        s = cell.summary
        got = {k: float(s[k]) for k in reference.COUNTERS if k in s}
        got["hit_requests"] = float(round(s["hit_rate"] * s["completed"]))
        got.update({k: float(v) for k, v in cell.pricing.items()})
        out.append(got)
    return out


def expected(requests: Sequence[Dict], deployment: Dict,
             cell: Dict[str, object], dtype: Optional[str] = None,
             pricing_dtype: Optional[str] = None) -> Dict[str, float]:
    """The reference's answer for one cell: the replay's counters and
    the pricing of the storm it implies."""
    served: List[Tuple[str, bool]] = []
    want = reference.replay(requests, deployment, cell, dtype, served)
    want.update(pricing.price(requests, deployment, served, pricing_dtype))
    return want


class Query:
    """One cell's query: built in set-up, run in the window, checked
    after it."""

    def __init__(self, deployment: Dict, query: Dict, seed: int) -> None:
        tr = deployment["trace"]
        self.deployment = deployment
        self.requests = traffic.seeded_trace(tr, tr["sites"], seed)
        self.axes = traffic.sweep_grid(query,
                                       traffic.touched_bytes(self.requests))
        self.cells = [reference_cell(deployment, p)
                      for p in traffic.grid_cells(self.axes)]
        self.spec = sweep_spec(deployment, self.requests, self.axes)
        self.serial_cells = 0

    def run(self) -> Dict:
        """One query through the public entry: its cell count, the
        requests that failed in its cells, the solver's counters and the
        answers."""
        from repro.core import run_sweep
        report = run_sweep(self.spec, batched=True)
        self.serial_cells += report.serial_cells
        failed = sum(int(c.summary["requests"]) - int(c.summary["completed"])
                     for c in report.cells)
        return {"cells": len(report.cells), "failed": failed,
                "solver": report.solver, "answers": answers(report)}

    def expected(self, dtype: Optional[str] = None,
                 pricing_dtype: Optional[str] = None
                 ) -> List[Dict[str, float]]:
        return [expected(self.requests, self.deployment, c, dtype,
                         pricing_dtype) for c in self.cells]

    def check(self, answered: Sequence[List[Dict[str, float]]]
              ) -> Dict[str, Dict[str, float]]:
        """Every answer held against the reference's."""
        return checks.compare(self.expected(), answered, self.serial_cells)


@contextlib.contextmanager
def wrapped(entries: Sequence[Tuple[str, str]],
            make: Callable[[str, Callable], Callable]) -> Iterator[None]:
    """Replace each ``module.name`` by ``make(name, original)`` for the
    duration of the block."""
    saved = []
    for mod_name, attr in entries:
        mod = importlib.import_module(mod_name)
        orig = getattr(mod, attr)
        saved.append((mod, attr, orig))
        setattr(mod, attr, make(attr, orig))
    try:
        yield
    finally:
        for mod, attr, orig in saved:
            setattr(mod, attr, orig)


def spans(log: List[Tuple[str, float, float]]):
    """Host spans around the four kernel-layer entries, appended to
    ``log`` as ``(entry, start, end)`` on ``time.perf_counter`` and
    written into the profiler's trace under the same names.  The
    entries return host arrays, so each span covers packing, the device
    call and the copy back."""
    import jax

    def make(name: str, orig: Callable) -> Callable:
        def timed(*args, **kwargs):
            t0 = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation(name):
                    return orig(*args, **kwargs)
            finally:
                log.append((name, t0, time.perf_counter()))
        return timed
    return wrapped(SPANS, make)
