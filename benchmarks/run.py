"""Benchmark harness — one module per paper table/figure + fleet benches.

Bench modules are auto-discovered: every ``benchmarks/bench_*.py`` that
exposes ``run() -> [(name, us_per_call, derived), ...]`` is picked up
(the old hard-coded import list silently skipped new benches).  Prints
``name,us_per_call,derived`` CSV rows.

A module that raises makes the harness exit non-zero *and* discards the
artifacts that module owns (its ``ARTIFACT_FILES`` names under
``benchmarks/artifacts/``): a failing bench used to leave whatever
artifact a previous run wrote — or a partial write — on disk, and the
CI regression gate would happily diff stale numbers.  No artifact is
better than a wrong one.

  python -m benchmarks.run                      # every bench
  python -m benchmarks.run --list               # discovered modules
  python -m benchmarks.run --only outage_storm  # substring/name select
  python -m benchmarks.run --only bench_micro --only bench_roofline

  bench_proxy_vs_stash   paper Table 3 + Figs 6–8 (4-download protocol)
  bench_wan_offload      paper Fig. 5 (Syracuse WAN collapse)
  bench_utilization      paper Table 1 + Fig. 4 (monitoring pipeline)
  bench_restart_storm    fleet: checkpoint fan-in through pod caches
  bench_fleet_scale      fleet: 1000-site storm, churn, eviction policies
  bench_outage_storm     fleet: simulator-native clients under outage storms
  bench_sweep            fleet: batched 216-cell scenario sweep vs serial
  bench_loader           fleet: federated training-data path
  bench_micro            federation hot-path micro-benchmarks
  bench_roofline         §Roofline terms from the dry-run artifacts
"""
from __future__ import annotations

import argparse
import importlib
import pkgutil
import sys
import traceback
from pathlib import Path
from typing import Dict, List, Optional


def discover() -> Dict[str, object]:
    """Import every ``bench_*`` module in this package, sorted by name."""
    import benchmarks
    names = sorted(m.name for m in pkgutil.iter_modules(benchmarks.__path__)
                   if m.name.startswith("bench_"))
    return {n: importlib.import_module(f"benchmarks.{n}") for n in names}


def discard_artifacts(mod: object) -> List[str]:
    """Remove the artifacts a failed bench owns so no stale (or
    truncated) JSON survives for downstream tooling to mistake for a
    fresh result.  Modules declare ownership via ``ARTIFACT_FILES``."""
    artifacts = Path(__file__).parent / "artifacts"
    removed: List[str] = []
    for name in getattr(mod, "ARTIFACT_FILES", ()):
        path = artifacts / name
        if path.exists():
            path.unlink()
            removed.append(name)
    return removed


def select(modules: Dict[str, object],
           only: Optional[List[str]]) -> Dict[str, object]:
    if not only:
        return modules
    picked: Dict[str, object] = {}
    for pat in only:
        want = pat if pat.startswith("bench_") else f"bench_{pat}"
        hits = {n: m for n, m in modules.items()
                if n == want or pat in n}
        if not hits:
            raise SystemExit(
                f"--only {pat!r} matched nothing; available: "
                f"{', '.join(modules)}")
        picked.update(hits)
    return picked


def main(argv: Optional[List[str]] = None) -> int:
    from repro.compile_cache import use_compile_cache
    use_compile_cache(Path(__file__).resolve().parent.parent)
    ap = argparse.ArgumentParser(
        prog="benchmarks.run", description=__doc__.splitlines()[0])
    ap.add_argument("--only", action="append", metavar="NAME",
                    help="run only benches whose module name matches "
                         "(exact bench_NAME or substring); repeatable")
    ap.add_argument("--list", action="store_true",
                    help="list discovered bench modules and exit")
    args = ap.parse_args(argv)
    modules = discover()
    if args.list:
        for n in modules:
            print(n)
        return 0
    print("name,us_per_call,derived")
    failed = 0
    for name, mod in select(modules, args.only).items():
        try:
            for row_name, us, derived in mod.run():
                print(f"{row_name},{us:.1f},{derived}", flush=True)
        except Exception as e:  # noqa: BLE001
            failed += 1
            print(f"{name},ERROR,{type(e).__name__}:{e}", flush=True)
            traceback.print_exc(file=sys.stderr)
            removed = discard_artifacts(mod)
            if removed:
                print(f"{name}: discarded stale artifacts "
                      f"{', '.join(removed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
