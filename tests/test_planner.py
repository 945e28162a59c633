"""Predictive planner: fitted cache models (forward accuracy against
exact replays, gradient flow) and the inverse capacity optimizer
(feasibility by exact-replay verification, savings vs uniform sizing),
plus the SweepAggregator validation surfaces they publish through."""
import dataclasses
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (FederationSpec, PlannerSpec, ScenarioSpec,
                        SweepAggregator, SweepSpec, WorkloadSpec,
                        apply_capacities, generate_workload,
                        groups_for_federation, plan_capacity, predict,
                        run_sweep, verify_plan)
from repro.kernels.cache_model import (ReuseHistogram, fit_interp_model,
                                       fit_lognormal_mixture,
                                       fleet_hit_rate, predict_hit_rate,
                                       predict_miss_bytes, reuse_histogram,
                                       stack_models)

CAP_AXIS = "federation.cache_capacity"


def chunk_hit(summary):
    """Chunk-level hit rate — the fraction the models predict (the
    request-level ``summary['hit_rate']`` mixes multi-chunk files)."""
    refs = summary["cache_hits"] + summary["cache_misses"]
    return summary["cache_hits"] / max(refs, 1)


def base_spec(n_requests=260, **fed_kw):
    fed_kw.setdefault("num_pods", 2)
    fed_kw.setdefault("hosts_per_pod", 2)
    fed_kw.setdefault("cache_capacity", 2e9)
    return ScenarioSpec(
        name="cell", engine="analytic",
        federation=FederationSpec.fleet(**fed_kw),
        workload=WorkloadSpec(kind="zipf", n_requests=n_requests,
                              working_set=8, duration=600.0, seed=5))


def hetero_spec():
    """Two pods with very different locality: pod0 hot and skewed,
    pod1 mostly cold — the planner should starve pod1."""
    fed = FederationSpec.fleet(num_pods=2, hosts_per_pod=2,
                               cache_capacity=2e9)
    wl = (generate_workload([fed.sites[0].name], 700, seed=0,
                            working_set=6, zipf_a=1.6)
          + generate_workload([fed.sites[1].name], 150, seed=1,
                              working_set=64, zipf_a=1.05))
    wl.sort(key=lambda r: r.time)
    return ScenarioSpec(name="hetero", engine="analytic",
                        federation=fed, workload=wl)


@pytest.fixture(scope="module")
def fit_report():
    grid = list(np.geomspace(4e8, 2e10, 6))
    return run_sweep(SweepSpec(name="fit", base=base_spec(),
                               axes={CAP_AXIS: grid}), fit=True)


@pytest.fixture(scope="module")
def hetero_fit():
    base = hetero_spec()
    rep = run_sweep(SweepSpec(name="hfit", base=base, axes={}), fit=True)
    return base, rep


class TestFitSweep:
    def test_fit_attaches_models_and_histograms(self, fit_report):
        models = fit_report.fitted_models()
        hists = fit_report.reuse_histograms()
        assert models and set(models) == set(hists)
        assert all(m.kind == "hist" for m in models.values())
        assert fit_report.summary()["fitted_cells"] == len(fit_report.cells)
        assert fit_report.summary()["solver"]["fit_streams"] >= len(models)
        # the histogram dicts are JSON-safe (what a dashboard ingests)
        json.dumps(hists)

    def test_fit_off_by_default(self):
        rep = run_sweep(SweepSpec(name="nofit", base=base_spec(60),
                                  axes={}))
        assert rep.fitted_models() == {}
        assert rep.reuse_histograms() == {}
        assert rep.summary()["fitted_cells"] == 0

    def test_histogram_conservation(self, fit_report):
        """Bucketed mass + compulsory mass = totals, exactly."""
        for d in fit_report.reuse_histograms().values():
            h = ReuseHistogram.from_dict(d)
            assert h.ref_weights.sum() + h.compulsory_refs == pytest.approx(
                h.total_refs)
            assert h.byte_weights.sum() + h.compulsory_bytes == (
                pytest.approx(h.total_bytes, rel=1e-9))

    def test_histogram_roundtrip(self):
        rng = np.random.default_rng(0)
        dist = rng.exponential(1e9, 500)
        dist[rng.random(500) < 0.2] = np.inf
        sizes = rng.integers(1, 1e8, 500).astype(float)
        h = reuse_histogram(dist, sizes)
        h2 = ReuseHistogram.from_dict(h.to_dict())
        np.testing.assert_allclose(h2.edges, h.edges)
        np.testing.assert_allclose(h2.ref_weights, h.ref_weights)
        assert h2.total_refs == h.total_refs


class TestForwardAccuracy:
    def test_heldout_grid_within_two_percent(self, fit_report):
        """The acceptance gate: the fitted curves never saw the swept
        capacities (they come from capacity-independent distances), so
        every grid cell is held out."""
        models = fit_report.fitted_models()
        errs = []
        for c in fit_report.cells:
            pred = predict(models, c.params[CAP_AXIS])["hit_rate"]
            errs.append(abs(pred - chunk_hit(c.summary)))
        assert max(errs) <= 0.02

    def test_mixture_compact_signature(self, fit_report):
        """The parametric mixture trades accuracy for compactness:
        looser band, but still monotone and close."""
        hists = {k: ReuseHistogram.from_dict(d)
                 for k, d in fit_report.reuse_histograms().items()}
        models = {k: fit_lognormal_mixture(h) for k, h in hists.items()}
        assert all(m.kind == "mixture" for m in models.values())
        errs = []
        for c in fit_report.cells:
            pred = predict(models, c.params[CAP_AXIS])["hit_rate"]
            errs.append(abs(pred - chunk_hit(c.summary)))
        assert max(errs) <= 0.04

    def test_fifo_interp_heldout(self):
        """FIFO columns are out of the stack model's reach; the interp
        model fits exact swept points and interpolates between them.
        FIFO hit curves are genuine staircases (whole hot objects cross
        the boundary at once), so midpoint interpolation carries a few
        points of error the ≤2% gate on the smooth LRU models does not
        — the band here covers the worst step."""
        spec = base_spec()
        fed = dataclasses.replace(spec.federation, sites=[
            dataclasses.replace(s, eviction_policy="fifo")
            if s.has_cache else s for s in spec.federation.sites])
        spec = dataclasses.replace(spec, federation=fed)
        grid = list(np.geomspace(4e8, 2e10, 13))
        rep = run_sweep(SweepSpec(name="fifo", base=spec,
                                  axes={CAP_AXIS: grid}))
        pts = [(c.params[CAP_AXIS], chunk_hit(c.summary))
               for c in rep.cells]
        train, held = pts[::2], pts[1::2]
        model = fit_interp_model([p[0] for p in train],
                                 [p[1] for p in train])
        errs = [abs(float(predict_hit_rate(model, cap)) - h)
                for cap, h in held]
        assert max(errs) <= 0.06

    def test_interp_exact_at_knots(self):
        model = fit_interp_model([1e9, 4e9, 1e10], [0.1, 0.4, 0.6])
        for cap, h in ((1e9, 0.1), (4e9, 0.4), (1e10, 0.6)):
            assert float(predict_hit_rate(model, cap)) == pytest.approx(
                h, abs=1e-6)
        # clipped, not extrapolated, outside the knots
        assert float(predict_hit_rate(model, 1e6)) == pytest.approx(0.1)
        assert float(predict_hit_rate(model, 1e14)) == pytest.approx(0.6)

    def test_miss_bytes_complements_hits(self, fit_report):
        """At huge capacity only compulsory bytes miss; at tiny
        capacity everything does."""
        for m in fit_report.fitted_models().values():
            tiny = float(predict_miss_bytes(m, 1.0))
            huge = float(predict_miss_bytes(m, 1e18))
            assert tiny == pytest.approx(m.total_bytes, rel=1e-3)
            assert huge == pytest.approx(m.compulsory_bytes, rel=1e-3)


class TestGradients:
    def test_grad_flows_through_fleet_hit_rate(self, fit_report):
        models = fit_report.fitted_models()
        stacked = stack_models(models)
        with jax.enable_x64():
            def fleet(logc):
                return fleet_hit_rate(stacked, jnp.exp(logc))

            g = jax.grad(fleet)(jnp.full(len(stacked.names),
                                         np.log(2e9), jnp.float64))
            g = np.asarray(g)
        assert np.isfinite(g).all()
        assert (g > 0).all()   # more capacity never hurts

    def test_predict_matches_stacked(self, fit_report):
        models = fit_report.fitted_models()
        stacked = stack_models(models)
        caps = {n: 3e9 for n in models}
        with jax.enable_x64():
            fleet = float(fleet_hit_rate(
                stacked, jnp.asarray([caps[n] for n in stacked.names],
                                     jnp.float64)))
        # predict() evaluates in default f32, the stacked path in f64
        assert predict(models, caps)["hit_rate"] == pytest.approx(
            fleet, abs=1e-5)


class TestAggregatorSurfaces:
    def _agg(self):
        agg = SweepAggregator()
        for policy in ("lru", "fifo"):
            for i, cap in enumerate((1e9, 2e9, 4e9)):
                agg.add({"federation.eviction_policy": policy,
                         CAP_AXIS: cap},
                        {"hit_rate": 0.2 + 0.1 * i
                         + (0.05 if policy == "lru" else 0.0),
                         "evictions": 10, "bytes_evicted": 100,
                         "admission_rejects": 0})
        return agg

    def test_hit_rate_curve_matches_policy_marginals(self):
        """Averaging a policy's curve points reproduces that policy's
        marginal — same rows, two views."""
        agg = self._agg()
        curves = {c[0]["federation.eviction_policy"]: c[1]
                  for c in agg.hit_rate_curve()}
        marginals = {row[0]: row[2] for row in agg.policy_marginals()}
        assert set(curves) == set(marginals)
        for policy, pts in curves.items():
            assert [p[0] for p in pts] == [1e9, 2e9, 4e9]   # sorted
            mean = sum(v for _, v in pts) / len(pts)
            assert mean == pytest.approx(marginals[policy])

    def test_hit_rate_curve_no_capacity_axis(self):
        agg = SweepAggregator()
        agg.add({"workload.seed": 1}, {"hit_rate": 0.5})
        assert agg.hit_rate_curve() == []

    def test_model_residuals(self):
        agg = self._agg()

        def pred(params):
            if params["federation.eviction_policy"] != "lru":
                return None
            return 0.3

        rows = agg.model_residuals(pred)
        assert len(rows) == 3   # fifo cells skipped
        for params, observed, predicted, residual in rows:
            assert predicted == 0.3
            assert residual == pytest.approx(predicted - observed)


class TestInversePlanner:
    def test_plan_feasible_and_beats_uniform(self, hetero_fit):
        base, rep = hetero_fit
        models = rep.fitted_models()
        groups = groups_for_federation(base.federation.build(), models)
        spec = PlannerSpec(models=models, target_hit_rate=0.5,
                           groups=groups)
        plan = plan_capacity(spec)
        assert plan.predicted_hit_rate >= 0.5
        assert set(plan.capacities) == set(groups)
        assert set(plan.per_cache) == set(models)
        ver = verify_plan(plan, base)
        assert ver.verification["feasible"]
        assert ver.verification["achieved_hit_rate"] >= 0.5
        assert ver.verification["executor"] == "batched"
        # the asymmetric optimum is far cheaper than uniform sizing
        assert ver.savings_vs_uniform > 0.2
        assert ver.total_capacity < ver.uniform_total

    def test_plan_summary_schema(self, hetero_fit):
        base, rep = hetero_fit
        models = rep.fitted_models()
        plan = plan_capacity(PlannerSpec(models=models,
                                         target_hit_rate=0.4),
                             federation=base.federation.build())
        ver = verify_plan(plan, base)
        s = ver.summary()
        for key in ("capacities", "per_cache", "predicted_hit_rate",
                    "total_capacity", "uniform_total",
                    "savings_vs_uniform", "verification", "telemetry"):
            assert key in s
        assert s["verification"]["feasible"] in (True, False)
        json.dumps(s)

    def test_infeasible_target_reported_not_hidden(self, hetero_fit):
        """A target above the workload's compulsory-miss ceiling can
        never verify; the report says so instead of pretending."""
        base, rep = hetero_fit
        models = rep.fitted_models()
        plan = plan_capacity(PlannerSpec(models=models,
                                         target_hit_rate=0.99))
        ver = verify_plan(plan, base, max_attempts=2)
        assert not ver.verification["feasible"]
        assert ver.verification["attempts"] == 2

    def test_apply_capacities_roundtrip(self, hetero_fit):
        base, _ = hetero_fit
        caps = {s.name: 7e9 for s in base.federation.sites}
        fed = apply_capacities(base.federation, caps)
        assert all(s.cache_capacity == 7e9 for s in fed.sites
                   if s.name in caps)
        # untouched spec stays inert
        assert base.federation.sites[0].cache_capacity == 2e9

    def test_egress_budget_constrains(self, hetero_fit):
        base, rep = hetero_fit
        models = rep.fitted_models()
        loose = plan_capacity(PlannerSpec(models=models,
                                          target_hit_rate=0.4))
        tight = plan_capacity(PlannerSpec(
            models=models, target_hit_rate=0.4,
            target_egress_bytes=loose.predicted_egress_bytes * 0.8))
        assert tight.predicted_egress_bytes <= (
            loose.predicted_egress_bytes * 0.8 * 1.02)
        assert tight.total_capacity >= loose.total_capacity * 0.99
