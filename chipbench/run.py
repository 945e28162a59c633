#!/usr/bin/env python3
"""The chip benchmark: one cell of ``BENCHMARK.json``, one run.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell names a configuration (``configs/<config>.json``, the
deployment and its request trace) and a traffic mix
(``traffic/<mix>.json``, the query).  The mix's ``kind`` names the module
that drives the program for it (``kinds/<kind>.py``).  The run builds the
query from ``--seed``, answers it once to warm every program it will use
(set-up), then answers it again and again through the program's public
entry for up to ``--seconds`` (the window: whole queries, another
started only where it should end in time).  With ``--trace 1`` the
window runs under the profiler.  After the window every answer is
compared with the plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics, each read by
``metrics/<name>.py``), ``device`` and the numbers compared with their
limits.  With no TPU, or fewer chips than the cell asks for, it exits 1
and prints no result.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

from bench import checks  # noqa: E402

OUT = HERE / "out"
# The profiler records the window's first queries, until this many
# seconds of them are traced (at least one whole query).
TRACE_SECONDS = 2.0


def process_start() -> float:
    """When this process started, on ``time.monotonic``'s clock (Linux
    ``/proc``; the interpreter's own start-up is set-up too)."""
    with open("/proc/self/stat") as f:
        ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    started_since_boot = ticks / os.sysconf("SC_CLK_TCK")
    return time.monotonic() - (time.clock_gettime(time.CLOCK_BOOTTIME)
                               - started_since_boot)


class Run:
    """What a metric reader sees: the window's queries, the host spans
    around the kernel entries, the reduced trace, the set-up time."""

    def __init__(self) -> None:
        self.queries: List[Dict] = []
        self.spans: List = []
        self.trace: Optional[Dict] = None
        self.setup_s = 0.0
        self.compiles = 0

    @property
    def window_s(self) -> float:
        return self.queries[-1]["end"] - self.queries[0]["start"]


def load_reader(name: str) -> Callable[[Run], Optional[float]]:
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: Dict, cell: str, trace: bool) -> List[Dict]:
    """The cell's end-to-end metrics, or its per-layer ones: those that
    list it, or that list no cells and move a metric the cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def load_cell(name: str) -> Dict:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return {"bench": bench, "cell": cell,
            "deployment": json.loads((ROOT / config["file"]).read_text()),
            "query": json.loads(
                (HERE / "traffic" / f"{cell['traffic']}.json").read_text())}


def require_chips(chips: int) -> Optional[Dict]:
    """The device block of the result, or ``None`` (with the reason on
    standard error) where JAX finds no TPU or too few chips."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"no TPU: JAX found {len(devices)} {devices[0].platform} "
              f"device(s), the cell needs {chips} TPU chip(s); there is no "
              f"CPU fallback", file=sys.stderr)
        return None
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": chips}


def load_kind(name: str):
    """The module that drives the program for one kind of query."""
    path = HERE / "kinds" / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"no query kind {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        "chipbench_kind_" + name.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def measure(spec: Dict, seed: int, seconds: float, trace: bool,
            device: Dict, started: float) -> Dict:
    """Set-up, window and check for one cell; returns the result line."""
    import jax

    from bench import trace as tracing
    from bench.compile_log import CompileLog

    log = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log)
    kind = load_kind(spec["query"]["kind"])
    query = kind.Query(spec["deployment"], spec["query"], seed)
    built = time.monotonic() - started

    run = Run()
    answered: List[List[Dict]] = [query.run()["answers"]]      # warm-up
    failed_total = 0
    warmed = time.monotonic() - started

    trace_dir = OUT / "trace" / spec["cell"]["name"]
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir),
                                 profiler_options=tracing.options())
    spans = kind.spans(run.spans) if trace else contextlib.nullcontext()
    compiles0 = len(log.events)
    # What set-up left behind (imports, the trace, compiled programs)
    # stays alive through the window: keep it out of the collector's
    # full passes, which would otherwise scan it again and again.
    gc.collect()
    gc.freeze()
    run.setup_s = time.monotonic() - started
    t_end = time.perf_counter() + seconds
    tracing_on = trace
    last = 0.0
    with spans:
        # whole queries only: another starts where it should end in time
        while not run.queries or time.perf_counter() + last <= t_end:
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(tracing.QUERY_SPAN):
                got = query.run()
            t1 = time.perf_counter()
            last = t1 - t0
            run.queries.append({"start": t0, "end": t1,
                                "cells": got["cells"],
                                "solver": got["solver"]})
            answered.append(got["answers"])
            failed_total += got["failed"]
            if tracing_on and t1 - run.queries[0]["start"] >= TRACE_SECONDS:
                jax.profiler.stop_trace()
                tracing_on = False
    run.compiles = len(log.events) - compiles0
    gc.unfreeze()
    if tracing_on:
        jax.profiler.stop_trace()
    if trace:
        names = [attr for _, attr in kind.SPANS]
        events = tracing.load(str(trace_dir), names)
        (trace_dir / "events.json").write_text(json.dumps(events))
        run.trace = tracing.reduce(events)
    stats = jax.devices()[0].memory_stats() or {}
    device = dict(device, memory_peak_bytes=int(
        stats.get("peak_bytes_in_use", 0)))
    if trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
    del got

    compared = query.check(answered)
    metrics = {}
    for m in cell_metrics(spec["bench"], spec["cell"]["name"], trace):
        value = load_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": checks.correct(compared),
              "attempted": sum(q["cells"] for q in run.queries),
              "failed": failed_total, "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        result["breakdown"] = {"device_ops": run.trace["device_ops"],
                               "idle_gaps": run.trace["idle_gaps"]}
    walls = sorted(q["end"] - q["start"] for q in run.queries)
    print(f"window: {len(run.queries)} queries, {result['attempted']} cells "
          f"in {run.window_s:.3f} s; query seconds min "
          f"{walls[0]:.4f} median {statistics.median(walls):.4f} max "
          f"{walls[-1]:.4f}; backend compiles in the window: "
          f"{run.compiles}", file=sys.stderr)
    print(f"set-up: {run.setup_s:.3f} s; trace and query built at "
          f"{built:.3f} s, warm-up query done at {warmed:.3f} s",
          file=sys.stderr)
    result["compared"] = {k: {"value": plain(v["value"]),
                              "limit": v["limit"]}
                          for k, v in compared.items()}
    return result


def plain(x: float):
    """A number as JSON holds it: NaN and infinities as text."""
    return x if math.isfinite(x) else str(x)


def main(argv=None) -> int:
    started = process_start()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"no program under test: {ROOT / 'src' / 'repro'} is missing",
              file=sys.stderr)
        return 2
    spec = load_cell(args.workload)
    if args.trace:
        from bench import trace as tracing
        # read when JAX first reaches the TPU, below
        os.environ["LIBTPU_INIT_ARGS"] = tracing.libtpu_args(
            os.environ.get("LIBTPU_INIT_ARGS", ""))
    # The compile cache lives inside this checkout, at a fixed path,
    # whatever the environment names: two checkouts share nothing.
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import use_compile_cache
    use_compile_cache(ROOT)
    import jax
    # cache every program, however quick to compile, so that a warm run
    # compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    device = require_chips(spec["cell"]["chips"])
    if device is None:
        return 1
    result = measure(spec, args.seed, args.seconds, bool(args.trace),
                     device, started)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
