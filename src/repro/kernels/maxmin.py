"""Vectorized max-min fair-share waterfilling (batched JAX array ops).

The fluid-flow simulator re-solves the max-min bandwidth allocation on
every flow arrival/completion.  The scalar solver walks python dicts of
links and flows — O(rounds × links × flows) per reallocation — which caps
:class:`~repro.core.simulator.FluidFlowSim` at a few hundred sites.  This
module batches the whole waterfilling across flows as array ops.

Topology paths are short (NIC → uplink → WAN → uplink → NIC, ≤ 5 links),
so membership is kept *sparse*: each flow carries a fixed-width row of
link indices, and every waterfilling round is a segment-sum (active flows
per link), a gather (each flow's tightest link share) and a scatter-add
(retiring capacity) under one ``lax.while_loop``:

  share_l   = cap_left_l / active_flows_l          (segment-sum)
  bottleneck = min_f min_{l ∈ links(f)} share_l    (gather + min)
  → fix flows whose own TCP cap binds below the bottleneck, else
  → fix every flow whose tightest share equals the bottleneck

Each round retires at least one flow or saturates at least one link; with
fleet-uniform link classes the shares are massively tied, so rounds stay
near the number of *distinct* bottleneck levels, not the link count.
Shapes are padded to power-of-two buckets so JIT recompiles O(log) times,
not per event.  ``repro.kernels.ref.maxmin_ref`` is the scalar oracle;
parity is enforced by ``tests/test_maxmin.py``.
"""
from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from .. import spans
from .batching import _next_pow2

_SPANS = spans.kernel("maxmin")


def solve_waterfill(link_caps: jax.Array, link_ids: jax.Array,
                    flow_caps: jax.Array) -> jax.Array:
    """The batchable waterfilling core (unjitted, vmappable).

    link_caps: (L,) with a trailing dummy-inf slot; link_ids: (F, K)
    int32 rows of link indices (padding points at the dummy slot);
    flow_caps: (F,) → per-flow rates (F,).

    Every op is shape-static and the while-loop body is idempotent once
    ``active`` empties, so ``jax.vmap(solve_waterfill)`` solves a whole
    batch of same-shaped problems in one call — that is what
    :mod:`repro.kernels.batched_maxmin` builds on for sweep pricing."""
    num_flows, width = link_ids.shape
    num_links = link_caps.shape[0]
    inf = jnp.float32(jnp.inf)
    flat_ids = link_ids.reshape(-1)

    def seg_sum(per_flow: jax.Array) -> jax.Array:
        """Scatter-add a per-flow value onto each of its links."""
        vals = jnp.broadcast_to(per_flow[:, None],
                                (num_flows, width)).reshape(-1)
        return jnp.zeros(num_links, per_flow.dtype).at[flat_ids].add(vals)

    def cond(state):
        _, active, _, it = state
        return jnp.logical_and(active.any(), it < num_flows + num_links + 2)

    def body(state):
        rates, active, cap_left, it = state
        n = seg_sum(active.astype(jnp.float32))
        share = jnp.where(n > 0, cap_left / jnp.maximum(n, 1.0), inf)
        flow_share = share[link_ids].min(axis=1)        # tightest link
        best = jnp.where(active, flow_share, inf).min()
        capped = active & (flow_caps < best)

        def fix(mask, rate):
            new_rates = jnp.where(mask, rate, rates)
            used = seg_sum(jnp.where(mask, rate, 0.0))
            return new_rates, active & ~mask, jnp.maximum(cap_left - used,
                                                          0.0)

        def fix_capped(_):
            return fix(capped, flow_caps)

        def fix_bottleneck(_):
            def no_links(_):
                # remaining flows cross no capacity-bearing link: their
                # own TCP cap is the only constraint (scalar fallback).
                return (jnp.where(active, flow_caps, rates),
                        jnp.zeros_like(active), cap_left)

            def waterfill(_):
                on_best = active & (flow_share <= best)
                new_rates, new_active, new_cap = fix(on_best, best)
                # float-safety: argmin links are saturated by construction
                return new_rates, new_active, jnp.where(share <= best, 0.0,
                                                        new_cap)

            return jax.lax.cond(jnp.isinf(best), no_links, waterfill, None)

        rates, active, cap_left = jax.lax.cond(
            capped.any(), fix_capped, fix_bottleneck, None)
        return rates, active, cap_left, it + 1

    rates0 = jnp.zeros_like(flow_caps)
    active0 = (link_ids < num_links - 1).any(axis=1)  # padded rows retired
    state = (rates0, active0, link_caps, jnp.int32(0))
    rates, _, _, _ = jax.lax.while_loop(cond, body, state)
    return rates


_solve = jax.jit(solve_waterfill)


def flow_bucket(num_flows: int) -> int:
    """The padded flow count ``maxmin_rates_sparse`` solves ``num_flows``
    flows at."""
    return _next_pow2(num_flows)


def pad_problem(link_caps: Sequence[float],
                flow_links: Sequence[Sequence[int]],
                flow_caps: Sequence[float],
                Fp: int, Lp: int, width: int):
    """Pad one (flows, links) problem into the ``solve_waterfill`` layout.

    Returns ``(caps, ids, fcaps)`` numpy arrays of shapes (Lp,), (Fp,
    width), (Fp,): real link capacities followed by infinite-capacity
    slots (the last is the dummy every padding id points at), per-flow
    link-index rows, zero-capped padding flows.  Shared by the
    single-problem path below and the pow2-bucketed batch packer in
    :mod:`repro.kernels.batched_maxmin`."""
    F, L = len(flow_links), len(link_caps)
    if L + 1 > Lp or F > Fp:
        raise ValueError(f"problem ({F} flows, {L} links) exceeds "
                         f"bucket (Fp={Fp}, Lp={Lp})")
    dummy = Lp - 1
    ids = np.full((Fp, width), dummy, np.int32)
    for fi, ls in enumerate(flow_links):
        if len(ls) > width:
            raise ValueError(f"flow {fi} crosses {len(ls)} links > "
                             f"bucket width {width}")
        ids[fi, :len(ls)] = ls
    caps = np.full(Lp, np.inf, np.float32)
    caps[:L] = link_caps
    fcaps = np.zeros(Fp, np.float32)
    fcaps[:F] = flow_caps
    return caps, ids, fcaps


def maxmin_rates_sparse(link_caps: Sequence[float],
                        flow_links: Sequence[Sequence[int]],
                        flow_caps: Sequence[float]) -> np.ndarray:
    """Max-min fair rates with per-flow caps, batched across the fleet.

    ``link_caps``: (L,) bytes/s; ``flow_links``: per-flow link-index
    lists; ``flow_caps``: (F,) per-flow TCP ceiling.  Shapes are padded
    to power-of-two buckets (padding points at a dummy infinite-capacity
    link slot) so the JIT cache stays small.  Each call writes the
    ``repro.kernel.maxmin`` spans (:mod:`repro.spans`).
    """
    F, L = len(flow_links), len(link_caps)
    call, pack, device, unpack = _SPANS
    with TraceAnnotation(call, flows=F):
        with TraceAnnotation(pack):
            width = _next_pow2(max((len(ls) for ls in flow_links),
                                   default=1), floor=4)
            Fp, Lp = flow_bucket(F), _next_pow2(L + 1)
            caps, ids, fcaps = pad_problem(link_caps, flow_links, flow_caps,
                                           Fp, Lp, width)
        with TraceAnnotation(device):
            rates = _solve(jnp.asarray(caps), jnp.asarray(ids),
                           jnp.asarray(fcaps))
            # slice on the host: slicing the device array compiles one
            # program per distinct flow count
            out = np.array(rates)[:F]
        with TraceAnnotation(unpack):
            # Flows crossing no capacity-bearing link (loopback transfers)
            # look identical to padding inside ``_solve`` — all-dummy rows
            # retired at rate 0 — but are real flows bound only by their
            # own TCP cap, which is what the scalar solver assigns.
            # Restore parity here so same-node ``sim.flow(src, src, ...)``
            # completes under both solvers.
            for fi, ls in enumerate(flow_links):
                if not ls:
                    out[fi] = flow_caps[fi]
    return out


def maxmin_rates(link_caps: np.ndarray, membership: np.ndarray,
                 flow_caps: np.ndarray) -> np.ndarray:
    """Dense-membership convenience wrapper: ``membership`` is (F, L) 0/1."""
    membership = np.asarray(membership)
    flow_links: List[List[int]] = [list(np.nonzero(row)[0])
                                   for row in membership]
    return maxmin_rates_sparse(np.asarray(link_caps, np.float32), flow_links,
                               np.asarray(flow_caps, np.float32))
