"""Request traces and what-if queries, generated from the data files of
a cell (``configs/<config>.json`` and ``traffic/<mix>.json``).

The trace generator is a copy of the program's Zipf/Table-2 generator
(``repro.core.workload.generate_workload`` with its
``PercentileSampler``), kept here so that no program change can move
the yardstick.  Its tables (paper Tables 1 and 2) come from the
configuration file.

A run's ``--seed`` must not change the amount of work: file sizes and
request counts decide the per-cache stream lengths, and those decide
the kernels' bucket shapes.  So the configuration fixes the trace's
*shape* with its own ``shape_seed`` (which files, which sizes, which
site asks for what, and the set of arrival times at each site), and the
run's seed only reassigns each site's arrival times among that site's
requests.  Every seed then replays the same multiset of references per
site, in another order, which is what moves hits, evictions and the
outage's reroutes.
"""
from __future__ import annotations

import bisect
import math
import random
from typing import Dict, List, Sequence, Tuple


class PercentileSampler:
    """File sizes from the piecewise log-linear Table 2 distribution."""

    def __init__(self, percentiles: Sequence[Sequence[float]],
                 top_bytes: float, seed: int) -> None:
        self._rng = random.Random(seed)
        pts = [(0.0, 512.0)] + [(p / 100.0, float(s))
                                for p, s in percentiles]
        pts.append((1.0, float(top_bytes)))
        self._ps = [p for p, _ in pts]
        self._ss = [s for _, s in pts]

    def sample(self) -> int:
        u = self._rng.random()
        i = min(bisect.bisect_right(self._ps, u) - 1, len(self._ps) - 2)
        p0, p1 = self._ps[i], self._ps[i + 1]
        s0, s1 = self._ss[i], self._ss[i + 1]
        frac = (u - p0) / (p1 - p0) if p1 > p0 else 0.0
        return max(1, int(math.exp(math.log(max(s0, 1.0)) * (1 - frac)
                                   + math.log(max(s1, 1.0)) * frac)))


def working_set(trace: Dict) -> List[Tuple[str, int]]:
    """Every file of the deployment's working set: ``(path, bytes)``,
    file ``k`` of each experiment in Table 1 order."""
    sampler = PercentileSampler(trace["file_size_percentiles"],
                                trace["file_size_top_bytes"],
                                trace["shape_seed"])
    return [(f"/{e}/data/file_{k:04d}", sampler.sample())
            for e in trace["experiment_bytes"]
            for k in range(trace["files_per_experiment"])]


def base_trace(trace: Dict, sites: Sequence[str]) -> List[Dict]:
    """The shape of the trace: Table 1 experiment mix, Zipf-popular
    files, uniform arrivals over the day, uniform sites — drawn from
    ``shape_seed`` exactly as the program's own generator draws it."""
    rng = random.Random(trace["shape_seed"])
    experiments = list(trace["experiment_bytes"])
    weights = [trace["experiment_bytes"][e] for e in experiments]
    nfiles = trace["files_per_experiment"]
    ranks = [1.0 / (k + 1) ** trace["zipf_a"] for k in range(nfiles)]
    files = working_set(trace)
    out: List[Dict] = []
    for _ in range(trace["requests"]):
        e_idx = rng.choices(range(len(experiments)), weights=weights)[0]
        k = rng.choices(range(nfiles), weights=ranks)[0]
        path, size = files[e_idx * nfiles + k]
        out.append({"time": rng.uniform(0.0, trace["duration_s"]),
                    "site": rng.choice(list(sites)),
                    "worker": rng.randrange(0, 1 << 16),
                    "path": path, "size": size,
                    "experiment": experiments[e_idx]})
    return out


def seeded_trace(trace: Dict, sites: Sequence[str], seed: int
                 ) -> List[Dict]:
    """The run's trace: the base trace with each site's arrival times
    dealt out again by ``seed`` among that site's requests of the same
    epoch.  Sorted by arrival time (ties by position), as the program
    replays it."""
    rng = random.Random(seed)
    reqs = [dict(r) for r in base_trace(trace, sites)]
    horizon = max(r["time"] for r in reqs) + trace["horizon_pad_s"]
    cuts = [f * horizon for f in trace["epoch_fractions"]]
    for site in sites:
        for epoch in range(len(cuts) + 1):
            mine = [r for r in reqs if r["site"] == site
                    and bisect.bisect_right(cuts, r["time"]) == epoch]
            times = [r["time"] for r in mine]
            rng.shuffle(times)
            for r, t in zip(mine, times):
                r["time"] = t
    reqs.sort(key=lambda r: r["time"])
    return reqs


def sweep_grid(query: Dict, ws_bytes: int) -> Dict[str, List]:
    """The sweep's axes, in order.  ``capacity_fractions`` become the
    ``federation.cache_capacity`` axis in whole bytes of the working set
    (an integer capacity, as every cache server holds it); any other
    axis is passed to the sweep as written."""
    axes: Dict[str, List] = {}
    for name, values in query["axes"].items():
        if name == "capacity_fractions":
            axes["federation.cache_capacity"] = [
                int(round(f * ws_bytes)) for f in values]
        else:
            axes[name] = list(values)
    return axes


def grid_cells(axes: Dict[str, List]) -> List[Dict[str, object]]:
    """The cross product of the axes, last axis fastest — the order in
    which the sweep reports its cells."""
    cells: List[Dict[str, object]] = [{}]
    for name, values in axes.items():
        cells = [dict(c, **{name: v}) for c in cells for v in values]
    return cells


def touched_bytes(requests: Sequence[Dict]) -> int:
    """Bytes of the distinct files a trace reads: the working set the
    caches see, against which capacities are set."""
    return sum({r["path"]: r["size"] for r in requests}.values())
