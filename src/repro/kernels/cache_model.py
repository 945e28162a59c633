"""Differentiable cache models: smoothed Mattson hit-rate curves.

:mod:`repro.kernels.stack_distance` answers *exact* hit/miss questions:
at capacity ``C``, reference ``i`` hits iff ``dist_i + size_i <= C``.
The distances are capacity-independent, so one kernel pass carries the
whole curve ``H(C)`` — but only as a step function, which autodiff
cannot use.  This module turns the same distances into *models*:

* :func:`reuse_histogram` — bucket the per-reference hit thresholds
  ``c_i = dist_i + size_i`` into log-spaced bins (reference counts and
  byte weights per bin, compulsory mass kept separate).  This is the
  per-cache ``reuse_histogram`` surfaced on sweep cells.
* ``kind="hist"`` models — the smoothed Mattson curve
  ``H(C) = Σ_b w_b · σ((ln C − ln d_b) / τ)`` over the histogram
  buckets: monotone non-decreasing in ``C``, bounded in ``[0, 1]``, and
  exact up to bucketing + smoothing error (τ → 0 recovers the step
  curve).  Differentiable in capacity everywhere.
* ``kind="mixture"`` models — a parametric mixture-of-lognormals CDF
  fitted to the empirical curve with a jitted Adam loop
  (:func:`fit_lognormal_mixture`): a compact per-workload signature
  that survives without the histogram.
* ``kind="interp"`` models — a monotone piecewise-linear spline in
  log-capacity through *exact* swept points
  (:func:`fit_interp_model`): the fallback for curves the LRU stack
  model does not express (FIFO victim order, admission-filtered
  residue), fitted at whatever level the caller measured.

Every model evaluates with plain ``jax.numpy`` — no host round-trips —
so hit rate, bytes-from-origin and per-tier egress are ``grad``-able in
capacity, which is what :mod:`repro.core.planner` differentiates
through.  :func:`stack_models` pads a fleet of per-cache models into
one ``(n_caches, B)`` problem so the planner's whole objective is a
single jitted expression.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_BUCKETS = 64
# Smoothing temperature in log-capacity space: ~5% capacity error per
# bucket edge, far below the 2%-absolute-hit-rate acceptance band.
DEFAULT_TAU = 0.05


# ---------------------------------------------------------------------------
# Reuse-distance histograms


@dataclasses.dataclass(frozen=True)
class ReuseHistogram:
    """Log-spaced histogram of per-reference hit thresholds.

    A reference with byte-weighted stack distance ``d`` and size ``s``
    hits at any capacity ``C >= d + s``; its *threshold* is ``c = d +
    s``.  Buckets carry reference counts and reference bytes; the
    compulsory mass (``d = inf``: first touch, cold restart) can never
    hit and is kept out of the buckets.
    """

    edges: np.ndarray         # (B+1,) threshold-bucket edges, bytes
    log_centers: np.ndarray   # (B,) mean ln(threshold) of refs in bucket
    ref_weights: np.ndarray   # (B,) references per bucket
    byte_weights: np.ndarray  # (B,) reference bytes per bucket
    compulsory_refs: int
    compulsory_bytes: int
    total_refs: int
    total_bytes: int

    def to_dict(self) -> Dict:
        """JSON-safe form (what sweep cells carry)."""
        return {
            "edges": [float(e) for e in self.edges],
            "log_centers": [float(c) for c in self.log_centers],
            "ref_weights": [float(w) for w in self.ref_weights],
            "byte_weights": [float(w) for w in self.byte_weights],
            "compulsory_refs": int(self.compulsory_refs),
            "compulsory_bytes": int(self.compulsory_bytes),
            "total_refs": int(self.total_refs),
            "total_bytes": int(self.total_bytes),
        }

    @staticmethod
    def from_dict(d: Dict) -> "ReuseHistogram":
        return ReuseHistogram(
            edges=np.asarray(d["edges"], np.float64),
            log_centers=np.asarray(d["log_centers"], np.float64),
            ref_weights=np.asarray(d["ref_weights"], np.float64),
            byte_weights=np.asarray(d["byte_weights"], np.float64),
            compulsory_refs=int(d["compulsory_refs"]),
            compulsory_bytes=int(d["compulsory_bytes"]),
            total_refs=int(d["total_refs"]),
            total_bytes=int(d["total_bytes"]))


def reuse_histogram(distances: np.ndarray, ref_sizes: np.ndarray,
                    n_buckets: int = DEFAULT_BUCKETS) -> ReuseHistogram:
    """Bucket one stream's hit thresholds ``c_i = dist_i + size_i``.

    ``distances`` come straight from
    :func:`repro.kernels.stack_distance.stack_distances_batch`
    (``inf`` marking compulsory misses); ``ref_sizes`` are the matching
    per-reference chunk bytes.  Totals are conserved exactly:
    ``sum(ref_weights) + compulsory_refs == total_refs`` and likewise
    for bytes — the property suite checks both.
    """
    dist = np.asarray(distances, np.float64)
    sizes = np.asarray(ref_sizes, np.float64)
    c = dist + sizes
    finite = np.isfinite(c)
    total_refs = int(len(c))
    total_bytes = int(round(sizes.sum()))
    comp_refs = int((~finite).sum())
    comp_bytes = int(round(sizes[~finite].sum()))
    cf, sf = c[finite], sizes[finite]
    if not len(cf):
        edges = np.geomspace(1.0, 2.0, n_buckets + 1)
        zeros = np.zeros(n_buckets)
        return ReuseHistogram(
            edges=edges, log_centers=np.log(np.sqrt(edges[:-1] * edges[1:])),
            ref_weights=zeros, byte_weights=zeros.copy(),
            compulsory_refs=comp_refs, compulsory_bytes=comp_bytes,
            total_refs=total_refs, total_bytes=total_bytes)
    lo, hi = float(cf.min()), float(cf.max())
    if hi <= lo:
        hi = lo * (1.0 + 1e-9) + 1.0
    edges = np.geomspace(lo, hi, n_buckets + 1)
    b = np.clip(np.searchsorted(edges, cf, side="right") - 1,
                0, n_buckets - 1)
    refw = np.bincount(b, minlength=n_buckets).astype(np.float64)
    bytew = np.bincount(b, weights=sf, minlength=n_buckets)
    logsum = np.bincount(b, weights=np.log(np.maximum(cf, 1.0)),
                         minlength=n_buckets)
    centers = np.log(np.sqrt(edges[:-1] * edges[1:]))
    occupied = refw > 0
    centers[occupied] = logsum[occupied] / refw[occupied]
    return ReuseHistogram(
        edges=edges, log_centers=centers, ref_weights=refw,
        byte_weights=bytew, compulsory_refs=comp_refs,
        compulsory_bytes=comp_bytes, total_refs=total_refs,
        total_bytes=total_bytes)


# ---------------------------------------------------------------------------
# Models


@dataclasses.dataclass(frozen=True)
class CacheModel:
    """One cache's fitted hit-rate curve, evaluable under autodiff.

    Every kind answers :func:`predict_hit_rate` /
    :func:`predict_miss_bytes` with pure ``jax.numpy`` math.  ``hist``
    and ``mixture`` kinds keep the histogram arrays (the mixture uses
    them for the byte/egress curve, where its ref-count fit does not
    apply); ``interp`` kinds carry only their knots.

    ``origin_fraction`` is the share of this cache's missed bytes that
    pulls from the *origin* rather than a parent tier (1.0 for flat
    caches and merged parent streams) — the per-tier egress weighting
    the planner's egress constraint uses.
    """

    kind: str                   # "hist" | "mixture" | "interp"
    tau: float = DEFAULT_TAU
    log_centers: Optional[np.ndarray] = None   # (B,)
    ref_weights: Optional[np.ndarray] = None   # (B,)
    byte_weights: Optional[np.ndarray] = None  # (B,)
    total_refs: float = 0.0
    total_bytes: float = 0.0
    compulsory_refs: float = 0.0
    compulsory_bytes: float = 0.0
    origin_fraction: float = 1.0
    # mixture-of-lognormals parameters (kind == "mixture")
    mix_logits: Optional[np.ndarray] = None     # (K,)
    mix_mu: Optional[np.ndarray] = None         # (K,)
    mix_log_sigma: Optional[np.ndarray] = None  # (K,)
    # monotone log-capacity spline knots (kind == "interp")
    knots_logc: Optional[np.ndarray] = None     # (M,)
    knots_hit: Optional[np.ndarray] = None      # (M,)
    fit_loss: float = 0.0


def fit_histogram_model(hist: ReuseHistogram, tau: float = DEFAULT_TAU,
                        origin_fraction: float = 1.0) -> CacheModel:
    """The smoothed Mattson curve over ``hist``'s buckets (nonparametric:
    the histogram *is* the fit)."""
    return CacheModel(
        kind="hist", tau=float(tau),
        log_centers=np.asarray(hist.log_centers, np.float64),
        ref_weights=np.asarray(hist.ref_weights, np.float64),
        byte_weights=np.asarray(hist.byte_weights, np.float64),
        total_refs=float(hist.total_refs),
        total_bytes=float(hist.total_bytes),
        compulsory_refs=float(hist.compulsory_refs),
        compulsory_bytes=float(hist.compulsory_bytes),
        origin_fraction=float(origin_fraction))


def _smoothed_frac(logC: jnp.ndarray, centers: jnp.ndarray,
                   weights: jnp.ndarray, tau: float) -> jnp.ndarray:
    """``Σ_b w_b σ((ln C − m_b)/τ)`` — broadcast over leading axes of
    ``logC``; weights need not be normalized."""
    z = (jnp.asarray(logC)[..., None] - centers) / tau
    return (weights * jax.nn.sigmoid(z)).sum(axis=-1)


def _mixture_cdf(logC: jnp.ndarray, logits: jnp.ndarray, mu: jnp.ndarray,
                 log_sigma: jnp.ndarray) -> jnp.ndarray:
    pis = jax.nn.softmax(logits)
    sigma = jnp.exp(log_sigma)
    z = (jnp.asarray(logC)[..., None] - mu) / (sigma * np.sqrt(2.0))
    return (pis * 0.5 * (1.0 + jax.scipy.special.erf(z))).sum(axis=-1)


def predict_hit_rate(model: CacheModel, capacity) -> jnp.ndarray:
    """``H(C)`` for one cache — differentiable in ``capacity`` (scalar
    or array), monotone non-decreasing, bounded in ``[0, 1]``."""
    logC = jnp.log(jnp.maximum(jnp.asarray(capacity, jnp.result_type(float)), 1.0))
    if model.kind == "interp":
        return jnp.clip(jnp.interp(logC, jnp.asarray(model.knots_logc),
                                   jnp.asarray(model.knots_hit)), 0.0, 1.0)
    denom = max(model.total_refs, 1.0)
    if model.kind == "mixture":
        finite = model.total_refs - model.compulsory_refs
        return finite / denom * _mixture_cdf(
            logC, jnp.asarray(model.mix_logits),
            jnp.asarray(model.mix_mu), jnp.asarray(model.mix_log_sigma))
    return _smoothed_frac(logC, jnp.asarray(model.log_centers),
                          jnp.asarray(model.ref_weights),
                          model.tau) / denom


def predict_miss_bytes(model: CacheModel, capacity) -> jnp.ndarray:
    """Expected bytes this cache pulls from upstream at ``capacity`` —
    the byte-weighted miss curve (compulsory bytes always pull)."""
    logC = jnp.log(jnp.maximum(jnp.asarray(capacity, jnp.result_type(float)), 1.0))
    if model.kind == "interp":
        return model.total_bytes * (1.0 - predict_hit_rate(model, capacity))
    hit_bytes = _smoothed_frac(logC, jnp.asarray(model.log_centers),
                               jnp.asarray(model.byte_weights), model.tau)
    return model.total_bytes - hit_bytes


# ---------------------------------------------------------------------------
# Parametric fit: mixture of lognormals


def _quantiles(values: np.ndarray, weights: np.ndarray,
               qs: np.ndarray) -> np.ndarray:
    order = np.argsort(values)
    v, w = values[order], weights[order]
    cw = np.cumsum(w)
    if cw[-1] <= 0:
        return np.zeros_like(qs)
    cw = cw / cw[-1]
    return np.interp(qs, cw, v)


@functools.partial(jax.jit, static_argnames=("steps", "lr"))
def _mixture_fit_loop(params0, grid, target, steps: int, lr: float):
    """Jitted Adam over the mixture parameters — the whole fit is one
    ``lax.fori_loop``, shared across every stream of a sweep (fixed
    grid/component shapes mean one compile)."""

    def loss_fn(params):
        logits, mu, log_sigma = params
        pred = _mixture_cdf(grid, logits, mu, log_sigma)
        return ((pred - target) ** 2).mean()

    grad_fn = jax.value_and_grad(loss_fn)

    def step(i, carry):
        params, mom, vel, _ = carry
        loss, grads = grad_fn(params)
        mom = jax.tree_util.tree_map(
            lambda a, g: 0.9 * a + 0.1 * g, mom, grads)
        vel = jax.tree_util.tree_map(
            lambda a, g: 0.999 * a + 0.001 * g * g, vel, grads)
        t = i + 1.0
        params = jax.tree_util.tree_map(
            lambda p, a, v: p - lr * (a / (1 - 0.9 ** t))
            / (jnp.sqrt(v / (1 - 0.999 ** t)) + 1e-8),
            params, mom, vel)
        return params, mom, vel, loss

    zeros = jax.tree_util.tree_map(jnp.zeros_like, params0)
    params, _, _, loss = jax.lax.fori_loop(
        0, steps, step, (params0, zeros, zeros,
                         jnp.zeros((), grid.dtype)))
    return params, loss


def fit_lognormal_mixture(hist: ReuseHistogram, components: int = 3,
                          steps: int = 400, lr: float = 0.08,
                          origin_fraction: float = 1.0,
                          stats: Optional[Dict] = None) -> CacheModel:
    """Fit ``H(C) = p · Σ_k π_k Φ((ln C − μ_k)/σ_k)`` to the empirical
    curve with a fully jitted Adam loop (``lax.fori_loop`` — zero host
    round-trips between steps).

    ``p`` is the pinned non-compulsory mass; the free parameters are
    the component logits, means and log-sigmas, initialised
    deterministically from weighted quantiles of the threshold
    distribution so the fit is reproducible run to run.
    """
    w = np.asarray(hist.ref_weights, np.float64)
    m = np.asarray(hist.log_centers, np.float64)
    mass = float(w.sum())
    if mass <= 0 or not np.isfinite(m).all():
        # no finite reuse: the curve is identically zero
        return CacheModel(
            kind="mixture", mix_logits=np.zeros(components),
            mix_mu=np.zeros(components), mix_log_sigma=np.zeros(components),
            total_refs=float(hist.total_refs),
            total_bytes=float(hist.total_bytes),
            compulsory_refs=float(hist.total_refs),
            compulsory_bytes=float(hist.compulsory_bytes),
            log_centers=m, ref_weights=w,
            byte_weights=np.asarray(hist.byte_weights, np.float64),
            origin_fraction=float(origin_fraction))
    # empirical CDF of the threshold distribution (normalized to the
    # finite mass — the compulsory scale factor is pinned, not fitted)
    grid = np.linspace(m.min() - 1.0, m.max() + 1.0, 129)
    target = np.array([(w * (m <= g)).sum() for g in grid]) / mass
    qs = (np.arange(components) + 0.5) / components
    mu0 = _quantiles(m, w, qs)
    spread = max(float(m.max() - m.min()), 0.1)
    with jax.enable_x64():
        params0 = (jnp.zeros(components, jnp.float64),
                   jnp.asarray(mu0, jnp.float64),
                   jnp.full(components,
                            np.log(spread / (2.0 * components)),
                            jnp.float64))
        params, loss = _mixture_fit_loop(params0, jnp.asarray(grid),
                                         jnp.asarray(target), steps, lr)
        logits, mu, log_sigma = (np.asarray(p, np.float64)
                                 for p in params)
    if stats is not None:
        stats["fit_steps"] = steps
        stats["fit_loss"] = float(loss)
    return CacheModel(
        kind="mixture", mix_logits=logits, mix_mu=mu,
        mix_log_sigma=log_sigma,
        total_refs=float(hist.total_refs),
        total_bytes=float(hist.total_bytes),
        compulsory_refs=float(hist.compulsory_refs),
        compulsory_bytes=float(hist.compulsory_bytes),
        log_centers=m, ref_weights=w,
        byte_weights=np.asarray(hist.byte_weights, np.float64),
        origin_fraction=float(origin_fraction), fit_loss=float(loss))


def fit_interp_model(capacities: Sequence[float],
                     hit_rates: Sequence[float],
                     total_refs: float = 1.0,
                     total_bytes: float = 0.0,
                     origin_fraction: float = 1.0) -> CacheModel:
    """Monotone piecewise-linear spline in log-capacity through exact
    swept ``(capacity, hit_rate)`` points — the model for curves the
    LRU stack does not express (FIFO columns, filtered residue).
    Monotonicity is enforced by a running max over the sorted knots, so
    the fitted curve keeps the property suite's invariants even when
    measurement noise wiggles the inputs."""
    caps = np.asarray(capacities, np.float64)
    hits = np.asarray(hit_rates, np.float64)
    order = np.argsort(caps)
    knots_logc = np.log(np.maximum(caps[order], 1.0))
    knots_hit = np.maximum.accumulate(np.clip(hits[order], 0.0, 1.0))
    return CacheModel(kind="interp", knots_logc=knots_logc,
                      knots_hit=knots_hit, total_refs=float(total_refs),
                      total_bytes=float(total_bytes),
                      origin_fraction=float(origin_fraction))


# ---------------------------------------------------------------------------
# Fleet-stacked evaluation (the planner's objective terms)


@dataclasses.dataclass(frozen=True)
class StackedModels:
    """A fleet of histogram-backed models padded to one ``(N, B)``
    problem, so fleet hit rate / egress at a capacity vector is a
    single jitted expression (and its gradient one VJP)."""

    names: List[str]
    log_centers: np.ndarray    # (N, B)
    ref_weights: np.ndarray    # (N, B)
    byte_weights: np.ndarray   # (N, B)
    total_refs: np.ndarray     # (N,)
    total_bytes: np.ndarray    # (N,)
    compulsory_bytes: np.ndarray  # (N,)
    origin_fraction: np.ndarray   # (N,)
    tau: float


def stack_models(models: Dict[str, CacheModel],
                 tau: Optional[float] = None) -> StackedModels:
    """Pad per-cache histogram models to a common bucket count.

    Only histogram-backed kinds stack (``hist`` and ``mixture`` — both
    carry bucket arrays); ``interp`` models have no buckets and raise.
    Padding buckets carry zero weight, so they change nothing.
    """
    names = sorted(models)
    for n in names:
        if models[n].log_centers is None:
            raise ValueError(
                f"model {n!r} (kind={models[n].kind!r}) has no histogram "
                "buckets; the stacked planner needs hist/mixture models")
    B = max(len(models[n].log_centers) for n in names)
    N = len(names)
    centers = np.zeros((N, B))
    refw = np.zeros((N, B))
    bytew = np.zeros((N, B))
    tot_r = np.zeros(N)
    tot_b = np.zeros(N)
    comp_b = np.zeros(N)
    of = np.ones(N)
    for i, n in enumerate(names):
        mdl = models[n]
        b = len(mdl.log_centers)
        centers[i, :b] = mdl.log_centers
        refw[i, :b] = mdl.ref_weights
        bytew[i, :b] = mdl.byte_weights
        tot_r[i] = mdl.total_refs
        tot_b[i] = mdl.total_bytes
        comp_b[i] = mdl.compulsory_bytes
        of[i] = mdl.origin_fraction
    return StackedModels(
        names=names, log_centers=centers, ref_weights=refw,
        byte_weights=bytew, total_refs=tot_r, total_bytes=tot_b,
        compulsory_bytes=comp_b, origin_fraction=of,
        tau=float(tau if tau is not None
                  else max(m.tau for m in models.values())))


def fleet_hits(stacked: StackedModels, capacities) -> jnp.ndarray:
    """Expected hit *count* per cache at a per-cache capacity vector
    ``(N,)`` — pure jnp, differentiable."""
    logC = jnp.log(jnp.maximum(jnp.asarray(capacities, jnp.result_type(float)), 1.0))
    z = (logC[:, None] - jnp.asarray(stacked.log_centers)) / stacked.tau
    return (jnp.asarray(stacked.ref_weights) * jax.nn.sigmoid(z)).sum(axis=1)


def fleet_hit_rate(stacked: StackedModels, capacities) -> jnp.ndarray:
    """Chunk-level fleet hit rate ``Σ hits_c / Σ refs_c`` at a
    per-cache capacity vector — the quantity the planner constrains
    (matches ``cache_hits / (cache_hits + cache_misses)`` of an exact
    replay, up to bucketing + smoothing error)."""
    total = jnp.maximum(jnp.asarray(stacked.total_refs).sum(), 1.0)
    return fleet_hits(stacked, capacities).sum() / total


def fleet_origin_egress(stacked: StackedModels, capacities) -> jnp.ndarray:
    """Expected origin egress bytes at a per-cache capacity vector:
    each cache's missed bytes (reuse misses + compulsory), weighted by
    the share of its misses that pulls from the origin rather than a
    parent tier."""
    logC = jnp.log(jnp.maximum(jnp.asarray(capacities, jnp.result_type(float)), 1.0))
    z = (logC[:, None] - jnp.asarray(stacked.log_centers)) / stacked.tau
    hit_bytes = (jnp.asarray(stacked.byte_weights)
                 * jax.nn.sigmoid(z)).sum(axis=1)
    miss_bytes = jnp.asarray(stacked.total_bytes) - hit_bytes
    return (jnp.asarray(stacked.origin_fraction) * miss_bytes).sum()
