"""From a profiler trace to the device numbers a run reports.

:func:`load` turns the profiler's ``.xplane.pb`` into plain lists of
``(name, start_ns, duration_ns)``: the device planes' XLA module and op
events, and the host spans the benchmark wrote (names starting with
``chipbench.`` or a kernel entry's name).  :func:`reduce` works on those
lists alone, so the tests can feed it a small recorded trace.

* busy: the union of the device's module intervals inside the window,
  averaged over the chips used;
* window: from the first query span's start to the last one's end;
* per-module device seconds: the summed durations of a module's events
  (module names are matched by prefix, as XLA appends an id);
* the device operations that took most time: the HLO ops where the
  trace holds them (an op inside a loop runs once per iteration; its
  time is summed), else the modules;
* idle gaps: the stretches of the window with no module running, each
  named by the innermost host span around its middle.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, duration_ns)

MODULE_LINES = ("XLA Modules",)
OP_LINES = ("XLA Ops",)
QUERY_SPAN = "chipbench.query"
# libtpu flag for traced runs: the device records one event per module
# run and none per HLO op.  Every op of every loop iteration is an event
# otherwise; a day query holds about 1e7 of them, over the profiler's
# 6.29e6-event buffer (the rest, the pricing with it, is dropped), and
# writing 1e7 takes the profiler some 240 s.  A flag of the compiler, so
# a traced run compiles its programs anew once per checkout.
HLO_TRACE_OFF = "--xla_enable_hlo_trace=false"


def libtpu_args(current: str) -> str:
    """``LIBTPU_INIT_ARGS`` for a traced run: what the environment gives,
    with the per-op trace turned off."""
    return f"{current} {HLO_TRACE_OFF}".strip()


def options():
    """Profiler options for the traced window: device activity and the
    benchmark's own host spans, without the Python call tracer (which
    records every function call of the host path and would both swell
    the trace and slow the window it measures)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def op_name(text: str) -> str:
    """A device op's HLO text shortened to its name and opcode
    (``%while.22 while``): the full text repeats every operand shape."""
    name, _, rest = text.partition(" = ")
    m = re.search(r" ([a-z][a-z0-9-]*)\(", rest)
    return f"{name} {m.group(1)}" if m else name


def load(trace_dir: str, host_names: Sequence[str]) -> Dict:
    """The trace under ``trace_dir`` as plain lists: the benchmark's host
    spans and each TPU's module events as ``(name, start_ns,
    duration_ns)``, and each TPU's op time summed by :func:`op_name`
    over the window of the query spans (ops run once per loop
    iteration, so there are millions of them)."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one trace file under {trace_dir}, "
                         f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    out: Dict = {"devices": {}, "host": []}
    tpus = [p for p in data.planes
            if p.name.startswith("/device:") and "TPU" in p.name]
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events
                    if e.name == QUERY_SPAN or e.name in host_names)
    win = window_ns(out)
    for plane in tpus:
        dev = out["devices"].setdefault(plane.name, {"modules": [],
                                                     "ops": {}})
        for line in plane.lines:
            if line.name in MODULE_LINES:
                dev["modules"].extend((e.name, float(e.start_ns),
                                       float(e.duration_ns))
                                      for e in line.events)
            elif line.name in OP_LINES and win is not None:
                ops = dev["ops"]
                lo, hi = win
                for e in line.events:
                    if lo <= e.start_ns < hi:
                        ops[e.name] = ops.get(e.name, 0.0) + e.duration_ns
        dev["ops"] = _shorten(dev["ops"])
    return out


def _shorten(ops: Dict[str, float]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for text, ns in ops.items():
        key = op_name(text)
        out[key] = out.get(key, 0.0) + ns * 1e-9
    return out


def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(events: Iterable[Event], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(s + d, hi)) for _, s, d in events
            if s + d > lo and s < hi]


def window_ns(events: Dict) -> Optional[Tuple[float, float]]:
    queries = [(s, s + d) for n, s, d in events["host"] if n == QUERY_SPAN]
    if not queries:
        return None
    return min(a for a, _ in queries), max(b for _, b in queries)


def reduce(events: Dict, top: int = 10) -> Optional[Dict]:
    """Busy and window seconds, the number of queries traced, per-module
    device seconds, the device operations (or, with no op events, the
    modules) that took most time, and the
    longest idle gaps, all over the window of the query spans.  ``None``
    when the trace holds no query span or no device."""
    win = window_ns(events)
    devices = events["devices"]
    if win is None or not devices:
        return None
    lo, hi = win
    busy = []
    modules: Dict[str, float] = {}
    ops: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    for dev in devices.values():
        spans = _union(_clip(dev["modules"], lo, hi))
        busy.append(sum(b - a for a, b in spans))
        edges = [lo] + [x for ab in spans for x in ab] + [hi]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
        for name, s, d in dev["modules"]:
            if s + d > lo and s < hi:
                modules[name] = modules.get(name, 0.0) + d
        for name, secs in dev["ops"].items():
            ops[name] = ops.get(name, 0.0) + secs
    if not ops:
        ops = {k: v * 1e-9 for k, v in modules.items()}
    n = len(devices)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "busy_s": sum(busy) / n * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "module_s": {k: v / n * 1e-9 for k, v in modules.items()},
        "queries": sum(1 for name, s, d in events["host"]
                       if name == QUERY_SPAN and s >= lo and s + d <= hi),
        "device_ops": [[k, v / n] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_host_at(events["host"], (a + b) / 2), (b - a) * 1e-9]
                      for a, b in longest],
    }


def _host_at(host: Sequence[Event], t: float) -> str:
    """The innermost benchmark span around ``t``: a kernel entry (its
    packing or copy back), the sweep's host path inside a query, or
    nothing (between queries)."""
    inner = [(d, n) for n, s, d in host if s <= t <= s + d]
    if not inner:
        return "between queries"
    name = min(inner)[1]
    return "sweep host path" if name == QUERY_SPAN else name


def module_seconds(reduced: Dict, prefixes: Sequence[str]) -> Optional[float]:
    """Summed device seconds of the modules whose names start with one
    of ``prefixes``; ``None`` where no such module ran."""
    hits = [v for k, v in reduced["module_s"].items()
            if any(k.startswith(p) for p in prefixes)]
    return sum(hits) if hits else None
