"""Predictive capacity planner: invert fitted cache models by autodiff.

The sweep engine *describes* configurations it has exactly replayed;
this module *prescribes*.  Given the per-cache differentiable models a
``fit=`` sweep produced (:mod:`repro.kernels.cache_model`), it answers
both directions:

* **forward** (:func:`predict`) — hit rate / origin egress at capacity
  points no sweep cell ever replayed, straight from the smoothed
  Mattson curves;
* **inverse** (:func:`plan_capacity`) — minimize total fleet capacity
  subject to a target fleet hit rate (and optionally an origin-egress
  budget), with one capacity variable per *site* (every cache of a
  site shares the ``SiteSpec.cache_capacity`` knob, including the
  backbone sites of an L1×L2 hierarchy).

The inverse solve is an augmented-Lagrangian gradient descent in
log-capacity, fully jitted — inner Adam rounds inside
``lax.fori_loop``, outer dual updates with a geometrically rising
penalty weight, zero host round-trips — then
a monotone *repair* bisection rescales the solution onto the
constraint surface (the smoothed curves are monotone in capacity, so
feasibility-by-scaling is exact on the model).  The same jitted solve
also bisects the minimal *uniform* capacity meeting the target, which
seeds the descent and prices the ``savings_vs_uniform`` headline.

Model-level feasibility is not replay-level feasibility (bucketing and
smoothing error, FIFO columns fitted by spline): recommendations are
**verified** by replaying the recommended point through the exact
batched kernels (:func:`verify_plan` → :func:`~repro.core.api.
run_sweep` with a single cell), scaling capacities up by a bounded
backoff until the exact replay meets the target — so a returned plan's
``verification`` block is ground truth, not model output.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.cache_model import (CacheModel, StackedModels,
                                       fleet_hit_rate, fleet_origin_egress,
                                       predict_hit_rate, predict_miss_bytes,
                                       stack_models)


@dataclasses.dataclass(frozen=True)
class PlannerSpec:
    """One inverse-planning problem.

    ``models`` maps cache-server name → fitted :class:`CacheModel`
    (histogram-backed kinds; what ``run_sweep(fit=True)`` returns).
    ``groups`` maps capacity-variable name → the cache names sharing
    that variable; by default every cache is its own variable, and
    :func:`groups_for_federation` builds the per-site grouping that
    matches the ``SiteSpec.cache_capacity`` knob.
    """

    models: Dict[str, CacheModel]
    target_hit_rate: float = 0.95
    target_egress_bytes: Optional[float] = None
    groups: Optional[Dict[str, List[str]]] = None
    min_capacity: float = 64e6
    max_capacity: float = 1e16
    steps: int = 600
    lr: float = 0.05
    penalty: float = 10.0           # initial augmented-Lagrangian weight ρ
    penalty_growth: float = 100.0   # final ρ = penalty * growth
    margin: float = 0.002           # plan for target + margin (smoothing slack)


@dataclasses.dataclass
class PlanReport:
    """What the planner recommends, plus how it got there.

    ``capacities`` are per group (per site under
    :func:`groups_for_federation`); ``per_cache`` expands groups to
    cache-server names.  ``verification`` is ``None`` until
    :func:`verify_plan` has replayed the point through the exact
    kernels."""

    capacities: Dict[str, float]
    per_cache: Dict[str, float]
    predicted_hit_rate: float
    predicted_egress_bytes: float
    total_capacity: float
    uniform_capacity: float
    uniform_total: float
    savings_vs_uniform: float
    target_hit_rate: float
    target_egress_bytes: Optional[float] = None
    wall_seconds: float = 0.0
    telemetry: Dict[str, float] = dataclasses.field(default_factory=dict)
    verification: Optional[Dict] = None

    def summary(self) -> Dict:
        """JSON-safe form — the ``plan.json`` artifact schema."""
        return {
            "capacities": {k: float(v) for k, v in self.capacities.items()},
            "per_cache": {k: float(v) for k, v in self.per_cache.items()},
            "predicted_hit_rate": float(self.predicted_hit_rate),
            "predicted_egress_bytes": float(self.predicted_egress_bytes),
            "total_capacity": float(self.total_capacity),
            "uniform_capacity": float(self.uniform_capacity),
            "uniform_total": float(self.uniform_total),
            "savings_vs_uniform": float(self.savings_vs_uniform),
            "target_hit_rate": float(self.target_hit_rate),
            "target_egress_bytes": (float(self.target_egress_bytes)
                                    if self.target_egress_bytes is not None
                                    else None),
            "wall_seconds": float(self.wall_seconds),
            "telemetry": {k: float(v) for k, v in self.telemetry.items()},
            "verification": dict(self.verification)
            if self.verification is not None else None,
        }


def groups_for_federation(fed, models: Dict[str, CacheModel]
                          ) -> Dict[str, List[str]]:
    """Site-name → cache-names grouping matching the per-site
    ``SiteSpec.cache_capacity`` knob (only caches with a fitted model
    count; a site whose caches saw no traffic gets no variable)."""
    out: Dict[str, List[str]] = {}
    for s in fed.sites:
        names = [n for n in s.cache_names() if n in models]
        if names:
            out[s.name] = names
    return out


def predict(models: Dict[str, CacheModel], capacities) -> Dict:
    """Forward mode: hit rate / egress at an *unswept* capacity point.

    ``capacities`` is a scalar (uniform) or a dict of cache name →
    bytes.  Works for every model kind (interp included), weighting
    per-cache curves by reference counts — so a fleet at heterogeneous
    capacities prices in one call, no replay."""
    names = sorted(models)
    caps = {n: float(capacities[n] if isinstance(capacities, dict)
                     else capacities) for n in names}
    hits = refs = egress = 0.0
    per_cache: Dict[str, float] = {}
    for n in names:
        mdl = models[n]
        h = float(predict_hit_rate(mdl, caps[n]))
        per_cache[n] = h
        w = max(mdl.total_refs, 1.0)
        hits += h * w
        refs += w
        egress += mdl.origin_fraction * float(predict_miss_bytes(mdl,
                                                                 caps[n]))
    return {"hit_rate": hits / max(refs, 1.0),
            "origin_egress_bytes": egress,
            "per_cache_hit_rate": per_cache}


def _solve(stacked: StackedModels, gidx: np.ndarray, gsize: np.ndarray,
           spec: PlannerSpec):
    """The jitted inverse solve.  Returns per-group capacities plus the
    uniform baseline and end-point telemetry, all computed on-device:
    bisection → augmented-Lagrangian Adam rounds → repair bisection."""
    target = spec.target_hit_rate + spec.margin
    budget = spec.target_egress_bytes
    lo, hi = np.log(spec.min_capacity), np.log(spec.max_capacity)
    G = len(gsize)
    gidx_j = jnp.asarray(gidx)
    gsize_j = jnp.asarray(gsize, jnp.float64)

    def hit_at(u):
        return fleet_hit_rate(stacked, jnp.exp(u)[gidx_j])

    def egress_at(u):
        return fleet_origin_egress(stacked, jnp.exp(u)[gidx_j])

    def feasible(u):
        ok = hit_at(u) >= target
        if budget is not None:
            ok = ok & (egress_at(u) <= budget)
        return ok

    def bisect(pred, ulo, uhi, iters=64):
        """Smallest scalar ``u`` in [ulo, uhi] with pred(u) true —
        pred monotone (hit rises, egress falls with capacity)."""
        def body(_, carry):
            a, b = carry
            mid = 0.5 * (a + b)
            good = pred(mid)
            return jnp.where(good, a, mid), jnp.where(good, mid, b)
        _, b = jax.lax.fori_loop(0, iters, body,
                                 (jnp.asarray(lo), jnp.asarray(hi))
                                 if ulo is None else (ulo, uhi))
        return b

    rounds = 8
    inner = max(spec.steps // rounds, 1)
    rho_growth = spec.penalty_growth ** (1.0 / max(rounds - 1, 1))

    @jax.jit
    def run():
        # uniform baseline: minimal single capacity meeting the target
        u_uni = bisect(lambda u: feasible(jnp.full(G, u)), None, None)
        u0 = jnp.full(G, u_uni)
        # normalize cost by the uniform total so its gradient is O(1/G)
        # — commensurate with the constraint term, which is what lets
        # Adam traverse *along* the constraint surface instead of
        # freezing at the first feasible point it touches
        scale = jnp.maximum((gsize_j * jnp.exp(u0)).sum(), 1.0)

        def cost(u):
            return (gsize_j * jnp.exp(u)).sum() / scale

        # augmented Lagrangian for the inequality constraints: the
        # multiplier term keeps a smooth restoring gradient even when
        # feasible (a one-sided quadratic penalty goes flat there, so
        # descent just slides back to uniform); at the stationary point
        # cost' = ν·h' per coordinate — the KKT marginal-value balance
        # that prices saturated caches down and hot caches up.
        def lagrangian(u, nu, nu2, rho):
            c = target - hit_at(u)
            aug = jnp.maximum(nu + rho * c, 0.0)
            val = cost(u) + (aug ** 2 - nu ** 2) / (2.0 * rho)
            if budget is not None:
                c2 = (egress_at(u) - budget) / max(budget, 1.0)
                aug2 = jnp.maximum(nu2 + rho * c2, 0.0)
                val = val + (aug2 ** 2 - nu2 ** 2) / (2.0 * rho)
            return val

        grad_fn = jax.grad(lagrangian)

        def outer(r, carry):
            u, mom, vel, nu, nu2, rho = carry

            def step(i, inner_carry):
                u, mom, vel = inner_carry
                g = grad_fn(u, nu, nu2, rho)
                mom = 0.9 * mom + 0.1 * g
                # β2=0.99: short second-moment memory, so one round's
                # constraint spike can't damp the next round's steps
                vel = 0.99 * vel + 0.01 * g * g
                t = r * inner + i + 1.0
                u = u - spec.lr * (mom / (1 - 0.9 ** t)) / (
                    jnp.sqrt(vel / (1 - 0.99 ** t)) + 1e-8)
                return jnp.clip(u, lo, hi), mom, vel

            u, mom, vel = jax.lax.fori_loop(0, inner, step, (u, mom, vel))
            nu = jnp.maximum(nu + rho * (target - hit_at(u)), 0.0)
            if budget is not None:
                nu2 = jnp.maximum(
                    nu2 + rho * (egress_at(u) - budget) / max(budget, 1.0),
                    0.0)
            return u, mom, vel, nu, nu2, rho * rho_growth

        u, _, _, _, _, _ = jax.lax.fori_loop(
            0, rounds, outer,
            (u0, jnp.zeros(G), jnp.zeros(G), jnp.asarray(0.0),
             jnp.asarray(0.0), jnp.asarray(float(spec.penalty))))
        # repair: rescale onto the constraint surface (monotone in the
        # global multiplier, so bisection is exact on the model)
        m = bisect(lambda s: feasible(u + s), jnp.asarray(-8.0),
                   jnp.asarray(8.0))
        u = jnp.clip(u + m, lo, hi)
        gnorm = jnp.linalg.norm(jax.grad(hit_at)(u))
        return (jnp.exp(u), jnp.exp(u_uni), hit_at(u), egress_at(u),
                gnorm)

    return run()


def plan_capacity(spec: PlannerSpec, federation=None) -> PlanReport:
    """Inverse planning: minimal total fleet capacity meeting
    ``spec.target_hit_rate`` (and the egress budget, if set).

    ``federation`` (a :class:`~repro.core.federation.FederationSpec`)
    switches the variables to per-site grouping via
    :func:`groups_for_federation` when ``spec.groups`` is unset.
    The returned report is model-level; chase it with
    :func:`verify_plan` for exact-replay ground truth."""
    t0 = time.perf_counter()
    groups = spec.groups
    if groups is None:
        groups = (groups_for_federation(federation, spec.models)
                  if federation is not None
                  else {n: [n] for n in spec.models})
    gnames = sorted(groups)
    stacked = stack_models(spec.models)
    pos = {n: i for i, n in enumerate(stacked.names)}
    gidx = np.zeros(len(stacked.names), np.int64)
    gsize = np.zeros(len(gnames))
    for gi, g in enumerate(gnames):
        for cache in groups[g]:
            gidx[pos[cache]] = gi
        gsize[gi] = len(groups[g])
    with jax.enable_x64():
        caps, uni, pred_hit, pred_egress, gnorm = (
            np.asarray(x, np.float64) for x in _solve(
                stacked, gidx, gsize, spec))
    capacities = {g: float(caps[gi]) for gi, g in enumerate(gnames)}
    per_cache = {cache: capacities[g]
                 for g in gnames for cache in groups[g]}
    total = float((gsize * caps).sum())
    uniform_total = float(gsize.sum() * uni)
    return PlanReport(
        capacities=capacities, per_cache=per_cache,
        predicted_hit_rate=float(pred_hit),
        predicted_egress_bytes=float(pred_egress),
        total_capacity=total, uniform_capacity=float(uni),
        uniform_total=uniform_total,
        savings_vs_uniform=1.0 - total / max(uniform_total, 1.0),
        target_hit_rate=spec.target_hit_rate,
        target_egress_bytes=spec.target_egress_bytes,
        wall_seconds=time.perf_counter() - t0,
        telemetry={"hit_grad_norm": float(gnorm),
                   "groups": float(len(gnames)),
                   "caches": float(len(stacked.names)),
                   "steps": float(spec.steps)})


def apply_capacities(fed, capacities: Dict[str, float]):
    """``fed`` with every named site's ``cache_capacity`` replaced —
    the bridge from a plan (per-site bytes) back to a runnable
    :class:`~repro.core.federation.FederationSpec`."""
    sites = [dataclasses.replace(s, cache_capacity=capacities[s.name])
             if s.name in capacities else s for s in fed.sites]
    return dataclasses.replace(fed, sites=sites)


def _exact_point(base, capacities: Dict[str, float]) -> Dict:
    """Replay one capacity point through the exact batched kernels."""
    from repro.core.api import SweepSpec, run_sweep
    cspec = dataclasses.replace(
        base, federation=apply_capacities(base.federation, capacities))
    report = run_sweep(SweepSpec(name="verify", base=cspec, axes={}))
    cell = report.cells[0]
    s = cell.summary
    refs = s["cache_hits"] + s["cache_misses"]
    return {"hit_rate": s["cache_hits"] / max(refs, 1),
            "origin_egress_bytes": s["origin_egress_bytes"],
            "executor": cell.executor}


def verify_plan(report: PlanReport, base, max_attempts: int = 6,
                scale: float = 1.25) -> PlanReport:
    """Ground-truth a plan against the exact batched kernels.

    Replays ``base`` (a :class:`~repro.core.api.ScenarioSpec`; its
    federation's site names must match the plan's group names) at the
    recommended capacities.  If the exact replay falls short of the
    target — model smoothing error — capacities scale up by ``scale``
    and replay again, at most ``max_attempts`` times, so the returned
    plan is *always* feasible when any capacity in range is (the
    property suite asserts this).  Returns the report with
    ``capacities``/``totals`` updated to the verified point and a
    ``verification`` block recording the evidence."""
    caps = dict(report.capacities)
    attempts = 0
    applied = 1.0
    exact: Dict = {}
    while True:
        attempts += 1
        exact = _exact_point(base, caps)
        ok = exact["hit_rate"] >= report.target_hit_rate
        if report.target_egress_bytes is not None:
            ok = ok and (exact["origin_egress_bytes"]
                         <= report.target_egress_bytes)
        if ok or attempts >= max_attempts:
            break
        caps = {k: v * scale for k, v in caps.items()}
        applied *= scale
    per_cache = {c: v * applied for c, v in report.per_cache.items()}
    total = sum(per_cache.values())
    return dataclasses.replace(
        report, capacities=caps, per_cache=per_cache,
        total_capacity=total,
        savings_vs_uniform=1.0 - total / max(report.uniform_total, 1.0),
        verification={
            "achieved_hit_rate": float(exact["hit_rate"]),
            "achieved_egress_bytes": float(exact["origin_egress_bytes"]),
            "target_hit_rate": float(report.target_hit_rate),
            "feasible": bool(exact["hit_rate"] >= report.target_hit_rate),
            "attempts": attempts,
            "scale_applied": applied,
            "executor": exact["executor"],
        })
