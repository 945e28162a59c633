"""The plain reference: one sweep cell replayed request by request.

A straightforward implementation of the federation's cache semantics
for a flat deployment (one cache per site, one origin), written from the
paper and the program's documented behaviour and importing nothing of
the program:

* a request from a site is served by the first live cache of that
  site's preference order (``cache_order`` in the configuration); the
  site's first cache locates the file's metadata whether it is up or
  not, and keeps it through restarts;
* a file is read as chunks of ``chunk_bytes`` (the last one short);
  each unavailable cache passed on the way counts one failover;
* a resident chunk is a hit (LRU moves it to the young end, FIFO does
  not); a miss pulls the chunk from the origin (origin egress) and then
  asks admission: with ``admission_max_fraction`` below 1 the cache
  refuses a file larger than that share of its capacity (judged on the
  file size where the cache has located the file, else on the chunk),
  a chunk larger than the whole capacity is refused, and otherwise the
  oldest chunks are evicted until the new one fits;
* an outage of ``outage_rate`` takes down the first ``ceil(rate × n)``
  caches at half the horizon (last arrival + 60 s) for a quarter of it,
  and they come back empty (a cold restart evicts nothing).

``dtype="float32"`` runs every byte quantity in float32: the control,
which breaks the deployment's guarantee of exact byte accounting.
"""
from __future__ import annotations

import math
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

# What a cell's answer is compared on: the summary's own names.
COUNTERS = ("cache_hits", "cache_misses", "evictions", "bytes_evicted",
            "admission_rejects", "origin_egress_bytes", "cache_failovers",
            "bytes_moved", "hit_requests")


class _Cache:
    def __init__(self, capacity, lru: bool, fraction: float, num) -> None:
        self.capacity = num(capacity)
        # the share of capacity is a float product, as admission takes it
        self.limit = (fraction * self.capacity if num is int
                      else np.float32(fraction) * self.capacity
                      ) if fraction < 1.0 else None
        self.lru = lru
        self.num = num
        self.chunks: "OrderedDict[tuple, object]" = OrderedDict()
        self.usage = num(0)
        self.located: set = set()
        self.up = True


def replay(requests: Sequence[Dict], deployment: Dict, cell: Dict,
           dtype: Optional[str] = None,
           served: Optional[List[Tuple[str, bool]]] = None
           ) -> Dict[str, float]:
    """Counters of one sweep cell over ``requests`` (sorted by arrival).

    ``cell`` holds ``capacity`` (bytes), ``policy`` (``lru``/``fifo``),
    ``admission`` (max object fraction) and ``outage_rate``.  Where
    ``served`` is given, each request appends to it the cache that
    served it and whether it missed a chunk there."""
    num = np.float32 if dtype == "float32" else int
    chunk = deployment["chunk_bytes"]
    names = deployment["caches"]
    caches = {n: _Cache(cell["capacity"], cell["policy"] == "lru",
                        cell["admission"], num) for n in names}
    events: List = []
    if cell["outage_rate"] > 0.0 and requests:
        k = min(len(names), max(1, math.ceil(cell["outage_rate"]
                                             * len(names))))
        horizon = max(r["time"] for r in requests) + 60.0
        events = ([(0.5 * horizon, n, False) for n in names[:k]]
                  + [(0.75 * horizon, n, True) for n in names[:k]])
        events.sort(key=lambda e: e[0])
    out = {c: num(0) if c in ("bytes_evicted", "origin_egress_bytes")
           else 0 for c in COUNTERS}
    ei = 0
    for r in requests:
        while ei < len(events) and events[ei][0] <= r["time"]:
            _, name, up = events[ei]
            c = caches[name]
            if up and not c.up:
                c.chunks.clear()          # cold restart: empty, no evictions
                c.usage = num(0)
            c.up = up
            ei += 1
        order = deployment["cache_order"][r["site"]]
        caches[order[0]].located.add(r["path"])
        passed = 0
        for name in order:
            if caches[name].up:
                break
            passed += 1
        else:
            raise ValueError(f"no live cache for {r['site']}: outside "
                             f"what this reference models")
        c = caches[order[passed]]
        size = r["size"]
        nchunks = max(1, -(-size // chunk))
        hits = misses = 0
        for j in range(nchunks):
            csize = num(min(chunk, size - j * chunk))
            key = (r["path"], j)
            out["cache_failovers"] += passed
            if key in c.chunks:
                hits += 1
                if c.lru:
                    c.chunks.move_to_end(key)
                continue
            misses += 1
            out["origin_egress_bytes"] += csize
            seen = num(size) if r["path"] in c.located else csize
            if c.limit is not None and seen > c.limit:
                out["admission_rejects"] += 1
                continue
            if csize > c.capacity:
                continue
            while c.usage + csize > c.capacity and c.chunks:
                _, gone = c.chunks.popitem(last=False)
                c.usage -= gone
                out["evictions"] += 1
                out["bytes_evicted"] += gone
            c.chunks[key] = csize
            c.usage += csize
        out["cache_hits"] += hits
        out["cache_misses"] += misses
        out["bytes_moved"] += size
        out["hit_requests"] += int(misses == 0 and hits > 0)
        if served is not None:
            served.append((order[passed], misses > 0))
    return {k: float(v) for k, v in out.items()}
