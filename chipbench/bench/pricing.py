"""The plain reference for a sweep cell's contention pricing.

Every cell's answer carries the price of its storm counterfactual: all
of the cell's transfers started at once on the deployment's network,
shared max-min fairly over the links.  The flows are

* one serve flow per request, from the cache that served it to the
  request's worker node (``<site>/worker<n>``, one per worker number),
  capped at ``serve_streams`` TCP windows per round trip, and at the
  cache's disk rate for a file larger than what it serves from memory;
* one pull flow per (cache, file) pair, at the first request that missed
  a chunk of that file there, from the origin to the cache, capped at
  ``pull_streams`` TCP windows per round trip.

A path runs from the source's NIC, through the two sites' uplinks and
the WAN where the sites differ, to the destination's NIC; its round
trip is twice the summed one-way latencies of those links.  The numbers
(``network`` in the configuration) are the deployment's link speeds.

The rates are the max-min fair allocation, found by progressive filling
in float64.  ``dtype="bfloat16"`` rounds every stored quantity to
bfloat16: the control, one precision below the program's float32.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

# What the pricing answer holds: the flow count is exact, the rest are
# compared by relative gap.
EXACT = ("peak_flows",)
RELATIVE = ("storm_finish_seconds", "min_rate", "mean_rate")

Flow = Tuple[List[str], float, float]      # (link names, cap, bytes)


def _rounder(dtype: Optional[str]):
    if dtype is None:
        return lambda x: x
    if dtype != "bfloat16":
        raise ValueError(f"no {dtype} pricing")
    import ml_dtypes
    bf16 = ml_dtypes.bfloat16
    return lambda x: np.asarray(x, np.float32).astype(bf16).astype(np.float64)


class Network:
    """Link speeds and latencies of one deployment (its ``network``)."""

    def __init__(self, deployment: Dict) -> None:
        net = deployment["network"]
        self.net = net
        self.window = float(net["tcp_window_bytes"])
        self.links: Dict[str, Tuple[float, float]] = {
            "wan": (net["wan"]["bandwidth"], net["wan"]["latency_s"])}
        lat = net["nic_latency_s"]
        for site, s in net["sites"].items():
            self.links[f"{site}/uplink"] = (s["site_uplink"],
                                            s["lan_latency_s"])
        for cache in deployment["caches"]:
            site = cache.split("/")[0]
            self.links[f"{cache}/nic"] = (net["sites"][site]["cache_nic"],
                                          lat)
        origin = net["origin"]
        self.origin = origin["node"]
        self.links[f"{self.origin}/nic"] = (origin["nic"], lat)

    def node_site(self, node: str) -> str:
        return node.split("/")[0]

    def path(self, src: str, dst: str) -> List[str]:
        a, b = self.node_site(src), self.node_site(dst)
        middle = [] if a == b else [f"{a}/uplink", "wan", f"{b}/uplink"]
        return [f"{src}/nic"] + middle + [f"{dst}/nic"]

    def worker(self, site: str, worker: int) -> str:
        node = f"{site}/worker{worker}"
        if f"{node}/nic" not in self.links:
            self.links[f"{node}/nic"] = (
                self.net["sites"][site]["worker_nic"],
                self.net["nic_latency_s"])
        return node

    def tcp_cap(self, path: Sequence[str], streams: int) -> float:
        rtt = 2.0 * sum(self.links[name][1] for name in path)
        return streams * self.window / max(rtt, 1e-6)


def flows(requests: Sequence[Dict], deployment: Dict,
          served: Sequence[Tuple[str, bool]]) -> Tuple[Network, List[Flow]]:
    """The storm's flows.  ``served[i]`` is the cache that served request
    ``i`` and whether it missed a chunk there (``reference.replay``)."""
    net = Network(deployment)
    n = net.net
    pulled = set()
    out: List[Flow] = []
    for r, (cache, missed) in zip(requests, served):
        size = float(r["size"])
        if missed and (cache, r["path"]) not in pulled:
            pulled.add((cache, r["path"]))
            path = net.path(net.origin, cache)
            out.append((path, net.tcp_cap(path, n["pull_streams"]), size))
        path = net.path(cache, net.worker(r["site"], r["worker"]))
        cap = net.tcp_cap(path, n["serve_streams"])
        disk = n["sites"][net.node_site(cache)]
        if disk["cache_disk_bw"] and size > disk["cache_mem_max"]:
            cap = min(cap, disk["cache_disk_bw"])
        out.append((path, cap, size))
    return net, out


def waterfill(link_caps: Sequence[float], flow_links: Sequence[Sequence[int]],
              flow_caps: Sequence[float], dtype: Optional[str] = None
              ) -> np.ndarray:
    """Max-min fair rates by progressive filling: raise every unfrozen
    flow's rate together until a link fills or a flow reaches its cap,
    freeze those flows at that level, and go on with the rest."""
    rnd = _rounder(dtype)
    nlinks = len(link_caps)
    width = max((len(ls) for ls in flow_links), default=1)
    ids = np.full((len(flow_links), max(width, 1)), nlinks, np.int64)
    for f, ls in enumerate(flow_links):
        ids[f, :len(ls)] = ls
    cap_left = rnd(np.append(np.asarray(link_caps, np.float64), np.inf))
    fcap = rnd(np.asarray(flow_caps, np.float64))
    rates = np.zeros(len(flow_links))
    active = np.ones(len(flow_links), bool)
    while active.any():
        count = np.bincount(ids[active].ravel(), minlength=nlinks + 1)
        share = np.full(nlinks + 1, np.inf)
        on = count[:nlinks] > 0
        share[:nlinks][on] = rnd(cap_left[:nlinks][on] / count[:nlinks][on])
        flow_share = share[ids].min(axis=1)
        level = min(flow_share[active].min(), fcap[active].min())
        if not np.isfinite(level):
            raise ValueError("a flow crosses no finite link and has no cap")
        done = active & ((fcap <= level) | (flow_share <= level))
        rates[done] = level
        used = np.bincount(ids[done].ravel(), minlength=nlinks + 1,
                           weights=np.full(ids[done].size, level))
        cap_left = rnd(np.maximum(cap_left - used, 0.0))
        cap_left[:nlinks][share[:nlinks] <= level] = 0.0
        cap_left[nlinks] = np.inf
        active &= ~done
    return rates


def price(requests: Sequence[Dict], deployment: Dict,
          served: Sequence[Tuple[str, bool]],
          dtype: Optional[str] = None) -> Dict[str, float]:
    """The cell's pricing answer: flow count, least and mean rate, and
    the storm's finish (the slowest flow's bytes over its rate)."""
    net, storm = flows(requests, deployment, served)
    if not storm:
        return {}
    index: Dict[str, int] = {}
    for path, _, _ in storm:
        for name in path:
            index.setdefault(name, len(index))
    caps = [net.links[name][0] for name in index]
    rates = waterfill(caps, [[index[n] for n in path] for path, _, _ in storm],
                      [cap for _, cap, _ in storm], dtype)
    rates = np.maximum(rates, 1e-9)
    nbytes = np.asarray([b for _, _, b in storm])
    return {"peak_flows": float(len(rates)), "min_rate": float(rates.min()),
            "mean_rate": float(rates.mean()),
            "storm_finish_seconds": float((nbytes / rates).max())}
