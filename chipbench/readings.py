#!/usr/bin/env python3
"""The readings a cell's limits are set from, for many seeds in one
process: the program's gaps to the reference (the lower reading) and
the control's (the upper reading).

    python3 chipbench/readings.py --workload <cell> --seeds 1,2,3

For each seed it answers the cell's query once through the program, at
the cell's own size, and answers every cell through the reference and
through the control: the same reference with every byte count in
float32 and the pricing in bfloat16, one precision below what the
deployment states for each.  It prints one JSON line per seed.  The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402
from bench import checks  # noqa: E402


def readings(spec, seed: int):
    kind = run.load_kind(spec["query"]["kind"])
    query = kind.Query(spec["deployment"], spec["query"], seed)
    got = query.run()["answers"]
    want = query.expected()
    control = query.expected(dtype="float32", pricing_dtype="bfloat16")
    program_gaps = checks.compare(want, [got], query.serial_cells)
    control_gaps = checks.compare(want, [control], 0)
    return {"seed": seed,
            "program": {k: v["value"] for k, v in program_gaps.items()},
            "control": {k: v["value"] for k, v in control_gaps.items()},
            "program_correct": checks.correct(program_gaps),
            "control_correct": checks.correct(control_gaps)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    spec = run.load_cell(args.workload)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    sys.path.insert(0, str(run.ROOT / "src"))
    from repro.compile_cache import use_compile_cache
    use_compile_cache(run.ROOT)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if run.require_chips(spec["cell"]["chips"]) is None:
        return 1
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(spec, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
