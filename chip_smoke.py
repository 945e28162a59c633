#!/usr/bin/env python3
"""Bring-up smoke run: the analytic engine's device path on one TPU chip.

Drives the federation's public entry points once, at a deployment-sized
stream, in one process, and checks every answer against the repo's own
references:

* **sweep** — ``run_sweep(batched=True, fit=True)`` over capacity ×
  eviction policy × admission × outage rate on a two-pod fleet, with
  every cache seeing at least 2**15 chunk references of a Zipf working
  set four times the largest capacity.  Reaches all three hit/miss
  kernels (stack distances, FIFO frontier, exact LRU state machine) and
  the batched max-min waterfill.
* **reference** — two of those cells (LRU and FIFO, admission < 1,
  under an outage) replayed serially through the ``CacheServer`` state
  machines; the summaries must be identical.
* **plan** — ``plan_capacity`` on the sweep's fitted models, then
  ``verify_plan``; plus a one-cell ``fit="mixture"`` sweep.
* **simulator** — a staggered checkpoint storm through ``FluidFlowSim``
  with ``solver="auto"`` at a peak of more than 1024 flows (so the
  jitted waterfill runs), against the same storm on the scalar solver.

Each phase prints its wall seconds and, apart from them, the XLA compile
seconds of the programs it compiled first.  The last line is one JSON
object naming the device.  There is no CPU fallback: with no TPU the
script exits non-zero before any phase.

    python3 chip_smoke.py [--seed N]
"""
from __future__ import annotations

import argparse
import json
import random
import sys
import time
from pathlib import Path
from typing import Dict, List, Tuple

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# Sweep: two pods, one cache each; ~4000 Table-2-sized requests put
# 2**15..2**16 chunk references on every cache (the outage cell's
# downed cache included), so every stream pads to one N bucket.
N_REQUESTS = 4000
WORKING_SET = 64                      # files per experiment (Table 1 mix)
CAPACITIES = [4e9, 32e9]
AXES = {
    "federation.cache_capacity": CAPACITIES,
    "federation.eviction_policy": ["lru", "fifo"],
    "federation.admission_max_fraction": [1.0, 0.25],
    "outage_rate": [0.0, 0.5],
}
MIN_STREAM_REFS = 2 ** 15
PARITY_KEYS = ("bytes_moved", "cache_hits", "cache_misses",
               "origin_egress_bytes", "evictions", "bytes_evicted",
               "admission_rejects")
REFERENCE_CELLS = [
    {"federation.cache_capacity": 4e9, "federation.eviction_policy": p,
     "federation.admission_max_fraction": 0.25, "outage_rate": 0.5}
    for p in ("lru", "fifo")]
TARGET_HIT_RATE = 0.6

# Simulator: 16 pods × 96 hosts pull one checkpoint, arriving in 50 ms
# slots over the first second.
STORM_PODS = 16
STORM_HOSTS = 96
STORM_BYTES = int(0.5e9)
STORM_WINDOW = 1.0
STORM_SLOT = 0.05
STORM_PEAK_AT = 1.0
MIN_PEAK_FLOWS = 1024
SIM_RTOL = 1e-4


class CompileLog:
    """XLA compile seconds per program, from JAX's monitoring events
    (register the instance as an event-duration listener)."""

    def __init__(self) -> None:
        self.events: List[Tuple[str, float]] = []

    def __call__(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((str(kw.get("fun_name", "?")), secs))


def require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


class Phase:
    """Prints one phase's wall seconds, the compile seconds inside them,
    and the rest."""

    def __init__(self, name: str, log: CompileLog) -> None:
        self.name = name
        self.log = log

    def __enter__(self) -> "Phase":
        self.t0 = time.perf_counter()
        self.c0 = len(self.log.events)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is None:
            wall = time.perf_counter() - self.t0
            new = self.log.events[self.c0:]
            comp = sum(s for _, s in new)
            progs = ", ".join(f"{n}={s:.2f}s" for n, s in new) or "none"
            print(f"[{self.name}] wall_s={wall:.2f} compile_s={comp:.2f} "
                  f"rest_s={wall - comp:.2f} compiled: {progs}", flush=True)
        return False


def base_scenario(seed: int):
    from repro.core import FederationSpec, ScenarioSpec, WorkloadSpec
    return ScenarioSpec(
        name="smoke", engine="analytic",
        federation=FederationSpec.fleet(num_pods=2, hosts_per_pod=8),
        workload=WorkloadSpec(kind="zipf", n_requests=N_REQUESTS,
                              working_set=WORKING_SET, duration=3600.0,
                              seed=seed))


def sweep_phase(log: CompileLog, base):
    from repro.core import SweepSpec, run_sweep
    ws_bytes = sum(base.workload.object_bytes().values())
    caps = AXES["federation.cache_capacity"]
    print(f"sweep: {len(SweepSpec('s', base, AXES))} cells, working set "
          f"{ws_bytes:.4e} B, capacities {caps}", flush=True)
    require(ws_bytes >= 4 * max(caps),
            f"working set {ws_bytes} >= 4 x largest capacity")
    with Phase("sweep", log):
        report = run_sweep(SweepSpec(name="smoke", base=base, axes=AXES),
                           batched=True, fit=True)
    s = report.solver
    print(f"sweep: batched_cells={report.batched_cells} "
          f"serial_cells={report.serial_cells} "
          f"stream_refs=[{s.get('min_stream_refs')}, "
          f"{s.get('max_stream_refs')}] fit_streams={s.get('fit_streams')}",
          flush=True)
    for kind in ("stack", "fifo", "cache_sim"):
        print(f"sweep: {kind}_calls={s.get(f'{kind}_calls', 0)} "
              f"problems={s.get(f'{kind}_problems', 0)} "
              f"padded={s.get(f'{kind}_padded', 0)} "
              f"buckets={s.get(f'{kind}_buckets', [])}", flush=True)
    print(f"sweep: solve_calls={s.get('solve_calls', 0)} "
          f"problems={s.get('problems', 0)} "
          f"padded={s.get('padded_problems', 0)} "
          f"buckets={s.get('buckets', [])}", flush=True)
    hits = [c.summary["hit_rate"] for c in report.cells]
    print(f"sweep: hit_rate [{min(hits):.4f}, {max(hits):.4f}], evictions "
          f"{sum(c.summary['evictions'] for c in report.cells)}", flush=True)
    require(report.serial_cells == 0, "serial_cells == 0")
    for key in ("stack_calls", "fifo_calls", "solve_calls",
                "cache_sim_calls"):
        require(s.get(key, 0) >= 1, f"{key} >= 1")
    require(s["min_stream_refs"] >= MIN_STREAM_REFS,
            f"every cache stream >= {MIN_STREAM_REFS} references")
    require(all(0.0 <= h <= 1.0 for h in hits), "hit rates in [0, 1]")
    return report


def reference_phase(log: CompileLog, base, report) -> None:
    from repro.core import SweepSpec, run_sweep
    with Phase("reference", log):
        for params in REFERENCE_CELLS:
            serial = run_sweep(
                SweepSpec(name="smoke", base=base,
                          axes={k: [v] for k, v in params.items()}),
                batched=False, price_contention=False).cells[0]
            batched = report.cell(**params)
            require(serial.executor == "serial"
                    and batched.executor == "batched", "executors")
            diff = {k: (batched.summary[k], serial.summary[k])
                    for k in PARITY_KEYS
                    if batched.summary[k] != serial.summary[k]}
            print(f"reference: {params['federation.eviction_policy']} "
                  f"cap={params['federation.cache_capacity']:.0e} "
                  f"admission={params['federation.admission_max_fraction']} "
                  f"outage={params['outage_rate']}: "
                  f"{'identical' if not diff else diff} on "
                  f"{len(PARITY_KEYS)} keys (hits "
                  f"{batched.summary['cache_hits']}, evictions "
                  f"{batched.summary['evictions']})", flush=True)
            require(not diff, f"batched == serial on {params}")


def plan_phase(log: CompileLog, base, report) -> None:
    import math

    from repro.core import (PlannerSpec, SweepSpec, groups_for_federation,
                            plan_capacity, run_sweep, verify_plan)
    models = report.fitted_models()
    groups = groups_for_federation(base.federation.build(), models)
    with Phase("plan", log):
        plan = plan_capacity(PlannerSpec(models=models,
                                         target_hit_rate=TARGET_HIT_RATE,
                                         groups=groups))
        plan = verify_plan(plan, base)
    v = plan.verification
    print(f"plan: target {TARGET_HIT_RATE} predicted_hit_rate="
          f"{plan.predicted_hit_rate:.4f} verified_hit_rate="
          f"{v['achieved_hit_rate']:.4f} feasible={v['feasible']} "
          f"attempts={v['attempts']} capacities="
          f"{ {k: f'{c:.4e}' for k, c in plan.capacities.items()} } "
          f"savings_vs_uniform={plan.savings_vs_uniform:.4f}", flush=True)
    require(v["feasible"] and v["executor"] == "batched", "plan verified")
    with Phase("mixture", log):
        mix = run_sweep(SweepSpec(name="smoke-mixture", base=base, axes={}),
                        fit="mixture")
    mods = mix.fitted_models()
    print("mixture: " + ", ".join(
        f"{n} kind={m.kind} fit_loss={m.fit_loss:.3e}"
        for n, m in sorted(mods.items())), flush=True)
    require(len(mods) == len(models)
            and all(m.kind == "mixture" and math.isfinite(m.fit_loss)
                    for m in mods.values()), "mixture models fitted")


def _storm(seed: int, solver: str):
    from repro.core import FluidFlowSim, build_fleet_federation, \
        stash_download
    fed = build_fleet_federation(num_pods=STORM_PODS,
                                 hosts_per_pod=STORM_HOSTS)
    origin = fed.origins[0]
    meta = origin.put_object("/ckpt/step_01000/params.npy", STORM_BYTES)
    sim = FluidFlowSim(fed.topology, fed.net, solver=solver)
    redirector = fed.redirectors.members[0].node.name
    rng = random.Random(seed)
    done: Dict[str, float] = {}
    for p in range(STORM_PODS):
        cache = fed.caches[f"pod{p}/cache"]
        for h in range(STORM_HOSTS):
            node = fed.client(f"pod{p}", h).node.name
            at = round(rng.uniform(0.0, STORM_WINDOW) / STORM_SLOT) \
                * STORM_SLOT
            sim.spawn(stash_download(sim, node, cache, origin.node.name,
                                     redirector, meta,
                                     fed.geoip.lookup_latency),
                      at=at, on_exit=lambda t, n=node: done.__setitem__(n, t))
    return sim, done


def sim_phase(log: CompileLog, seed: int) -> None:
    workers = STORM_PODS * STORM_HOSTS
    finish: Dict[str, Dict[str, float]] = {}
    for solver in ("auto", "scalar"):
        with Phase(f"simulator-{solver}", log):
            sim, done = _storm(seed, solver)
            sim.run(until=STORM_PEAK_AT)
            peak = len(sim.active)
            end = sim.run()
        print(f"simulator: solver={solver} workers={workers} "
              f"peak_flows={peak} reallocations={sim.reallocations} "
              f"end_s={end:.6f}", flush=True)
        require(len(done) == workers, f"{solver}: every worker done")
        if solver == "auto":
            require(peak >= MIN_PEAK_FLOWS,
                    f"peak {peak} >= {MIN_PEAK_FLOWS} flows")
            require(peak >= sim.vector_threshold, "vector solver taken")
        finish[solver] = done
    worst = max(abs(finish["auto"][n] - t) / max(abs(t), 1e-12)
                for n, t in finish["scalar"].items())
    print(f"simulator: max relative completion-time gap {worst:.3e} "
          f"(limit {SIM_RTOL})", flush=True)
    require(worst <= SIM_RTOL, "vector vs scalar completion times")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the workload and storm arrivals")
    args = ap.parse_args(argv)

    from repro.compile_cache import use_compile_cache
    cache_dir = use_compile_cache(ROOT)
    import jax
    log = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log)
    devices = jax.devices()
    dev = devices[0]
    print(f"device: jax {jax.__version__} platform={dev.platform} "
          f"kind={dev.device_kind} count={len(devices)} "
          f"compile_cache={cache_dir}", flush=True)
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform!r}; this smoke run has no "
              f"CPU fallback", file=sys.stderr)
        return 1

    base = base_scenario(args.seed)
    report = sweep_phase(log, base)
    reference_phase(log, base, report)
    plan_phase(log, base, report)
    sim_phase(log, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
