"""Cache hierarchies end to end: TierSpec/parent validation, the OSDF
preset, analytic-vs-sim per-tier byte parity, collapsed cache-to-cache
fill under a regional flash crowd, the two-round vectorized L1×L2 sweep
(zero serial cells, cell-exact against serial replay), tier sweep axes,
link-degradation scenarios, and the batched-executor regime gates."""
import dataclasses

import pytest

from repro.core import (FederationSpec, OutageSchedule, ScenarioSpec,
                        SiteSpec, SweepSpec, TierSpec, WorkloadSpec,
                        build_osdf_federation, run_scenario, run_sweep,
                        site_tiers)

PARITY_INTS = ("requests", "completed", "bytes_moved", "cache_hits",
               "cache_misses", "origin_egress_bytes", "parent_fill_bytes",
               "evictions", "bytes_evicted", "admission_rejects",
               "cache_failovers", "origin_fallbacks", "group_failovers",
               "outages", "recoveries")
PARITY_DICTS = ("tier_hits", "tier_misses", "tier_fill_bytes")
PARITY_FLOATS = ("hit_rate", "mean_seconds", "p50_seconds", "p95_seconds")

GB = 1000**3


def osdf_spec(n_requests=200, engine="analytic", **osdf_kw):
    osdf_kw.setdefault("edges_per_region", 2)
    osdf_kw.setdefault("workers_per_edge", 2)
    osdf_kw.setdefault("l1_capacity", 4 * GB)
    osdf_kw.setdefault("l2_capacity", 24 * GB)
    return ScenarioSpec(
        name="tiered", engine=engine,
        federation=FederationSpec.osdf(**osdf_kw),
        workload=WorkloadSpec(kind="zipf", n_requests=n_requests,
                              working_set=12, duration=600.0, seed=11))


# ---------------------------------------------------------------------------
# TierSpec / parent-graph validation
# ---------------------------------------------------------------------------
class TestTierSpec:
    def test_flatten_stamps_parent(self):
        tier = TierSpec(parent="backbone",
                        sites=[SiteSpec(name="a"), SiteSpec(name="b")])
        flat = tier.flatten()
        assert [s.parent for s in flat] == ["backbone", "backbone"]
        # originals untouched (flatten copies)
        assert all(s.parent is None for s in tier.sites)

    def test_site_tiers_depths(self):
        spec = FederationSpec.osdf(regions=("us-east", "us-west"))
        tiers = spec.site_tiers()
        assert tiers["us-east-edge0"] == 1
        assert tiers["us-west-edge1"] == 1
        assert tiers["us-east-backbone"] == 2
        assert "origin-facility" not in tiers  # cache-less
        assert spec.tier_depth() == 2

    def test_flat_federation_depth_one(self):
        assert FederationSpec.fleet(num_pods=2).tier_depth() == 1

    def test_unknown_parent_rejected(self):
        with pytest.raises(ValueError):
            site_tiers([SiteSpec(name="a", parent="ghost")])

    def test_cacheless_parent_rejected(self):
        with pytest.raises(ValueError):
            site_tiers([SiteSpec(name="a", parent="b"),
                        SiteSpec(name="b", has_cache=False)])

    def test_cacheless_child_with_parent_rejected(self):
        with pytest.raises(ValueError):
            site_tiers([SiteSpec(name="a", has_cache=False, parent="b"),
                        SiteSpec(name="b")])

    def test_parent_cycle_rejected(self):
        with pytest.raises(ValueError) as ei:
            site_tiers([SiteSpec(name="a", parent="b"),
                        SiteSpec(name="b", parent="a")])
        assert "cycle" in str(ei.value)

    def test_three_tier_chain(self):
        tiers = site_tiers([SiteSpec(name="edge", parent="mid"),
                            SiteSpec(name="mid", parent="top"),
                            SiteSpec(name="top")])
        assert tiers == {"edge": 1, "mid": 2, "top": 3}


# ---------------------------------------------------------------------------
# OSDF preset
# ---------------------------------------------------------------------------
class TestOsdfPreset:
    def test_build_shape(self):
        fed = build_osdf_federation()
        spec = FederationSpec.osdf()
        assert set(spec.cache_names()) == set(fed.caches)
        # CacheServer.tier stamped from the parent graph
        assert fed.caches["us-east-edge0/cache"].tier == 1
        assert fed.caches["us-east-backbone/cache"].tier == 2
        # edges fill from their regional backbone's ring
        edge = fed.caches["us-west-edge1/cache"]
        assert edge.parent_group is not None
        assert all(c.name.startswith("us-west-backbone")
                   for c in edge.parent_caches("/any/path"))
        # backbones are top tier: no parent
        assert fed.caches["us-east-backbone/cache"].parent_group is None

    def test_backbones_hold_no_workers(self):
        spec = FederationSpec.osdf()
        by_name = {s.name: s for s in spec.sites}
        assert by_name["us-east-backbone"].workers == 0
        assert by_name["origin-facility"].has_cache is False
        assert spec.origin_site == "origin-facility"


# ---------------------------------------------------------------------------
# Analytic vs simulated engine: per-tier byte parity
# ---------------------------------------------------------------------------
class TestTieredEngineParity:
    @pytest.fixture(scope="class")
    def summaries(self):
        # sequential single-flow chain with non-binding capacities: the
        # regime where both engines agree byte-for-byte (same framing as
        # TestEngineParity in test_api.py — eviction *timing* is engine-
        # specific; eviction-regime tiering is pinned by the batched-vs-
        # serial sweep parity below instead)
        spec = dataclasses.replace(
            osdf_spec(n_requests=200, engine="analytic",
                      l1_capacity=400 * GB, l2_capacity=400 * GB),
            sequential=True)
        analytic = run_scenario(spec).summary()
        sim = run_scenario(
            dataclasses.replace(spec, engine="sim")).summary()
        return analytic, sim

    def test_byte_exact_counters(self, summaries):
        analytic, sim = summaries
        for k in ("requests", "completed", "bytes_moved", "cache_hits",
                  "cache_misses", "origin_egress_bytes",
                  "parent_fill_bytes"):
            assert analytic[k] == sim[k], k
        for k in PARITY_DICTS:
            assert analytic[k] == sim[k], k

    def test_tier_counters_shape(self, summaries):
        analytic, _ = summaries
        assert set(analytic["tier_hits"]) == {"1", "2"}
        # edge misses fill from the parent tier, so tier-1 fill bytes
        # (bytes_from_parent + bytes_from_origin at tier 1) are positive
        assert analytic["tier_fill_bytes"]["1"] > 0
        assert analytic["parent_fill_bytes"] > 0
        # every origin byte egresses through the top tier
        assert analytic["tier_fill_bytes"]["2"] == \
            analytic["origin_egress_bytes"]

    def test_totals_cross_check(self, summaries):
        analytic, _ = summaries
        assert sum(analytic["tier_hits"].values()) == analytic["cache_hits"]
        assert sum(analytic["tier_misses"].values()) == \
            analytic["cache_misses"]


# ---------------------------------------------------------------------------
# Collapsed forwarding: a regional flash crowd fills cache-to-cache
# ---------------------------------------------------------------------------
class TestFlashCrowdEgress:
    @pytest.fixture(scope="class")
    def reports(self):
        tiered = osdf_spec(n_requests=120)
        flat_fed = dataclasses.replace(
            tiered.federation,
            sites=[dataclasses.replace(s, parent=None)
                   for s in tiered.federation.sites])
        crowd = WorkloadSpec(
            kind="flash_crowd", n_requests=120, working_set=12,
            duration=600.0, seed=11,
            hot_sites=("us-east-edge0", "us-east-edge1"),
            crowd_factor=4.0, crowd_at=60.0, crowd_duration=120.0,
            n_objects=3, size=500_000_000)
        t = run_scenario(dataclasses.replace(
            tiered, workload=crowd)).summary()
        f = run_scenario(dataclasses.replace(
            tiered, federation=flat_fed, workload=crowd)).summary()
        return t, f

    def test_tiered_fill_cuts_origin_egress(self, reports):
        tiered, flat = reports
        assert tiered["origin_egress_bytes"] < flat["origin_egress_bytes"]
        assert tiered["parent_fill_bytes"] > 0
        assert flat["parent_fill_bytes"] == 0

    def test_crowd_requests_present(self, reports):
        tiered, flat = reports
        assert tiered["requests"] == flat["requests"] > 120


# ---------------------------------------------------------------------------
# Two-round vectorized sweep: L1×L2 split-sizing with zero serial cells
# ---------------------------------------------------------------------------
class TestTierSweepParity:
    @pytest.fixture(scope="class")
    def reports(self):
        sweep = SweepSpec(name="l1xl2", base=osdf_spec(n_requests=60), axes={
            "federation.tier1.cache_capacity": [2 * GB, 6 * GB],
            "federation.tier2.cache_capacity": [4 * GB, 12 * GB, 24 * GB],
            "federation.eviction_policy": ["lru", "fifo"],
        })
        batched = run_sweep(sweep, batched=True)
        serial = run_sweep(sweep, batched=False, price_contention=False)
        return batched, serial

    def test_no_serial_cells(self, reports):
        batched, _ = reports
        assert len(batched.cells) == 12
        assert batched.batched_cells == len(batched.cells)
        assert batched.serial_cells == 0
        assert all(c.executor == "batched" for c in batched.cells)

    def test_two_kernel_rounds(self, reports):
        batched, _ = reports
        assert batched.solver.get("tier_rounds") == 2

    def test_every_cell_is_byte_exact(self, reports):
        batched, serial = reports
        for cb, cs in zip(batched.cells, serial.cells):
            assert cb.params == cs.params
            for k in PARITY_INTS:
                assert cb.summary[k] == cs.summary[k], (cb.params, k)
            for k in PARITY_DICTS:
                assert cb.summary[k] == cs.summary[k], (cb.params, k)
            for k in PARITY_FLOATS:
                assert cb.summary[k] == pytest.approx(cs.summary[k],
                                                      rel=1e-9), \
                    (cb.params, k)

    def test_split_sizing_moves_the_needle(self, reports):
        batched, _ = reports
        egress = {c.summary["origin_egress_bytes"] for c in batched.cells}
        assert len(egress) > 1  # the L1/L2 split actually matters

    def test_fit_covers_both_rounds(self, reports):
        """``fit=True`` queues each round's unfiltered variants: every
        child cache gets a model from round 1 and the parent-tier cache
        one from round 2, and the exact cells are what ``fit=False``
        gives."""
        batched, _ = reports
        spec = osdf_spec(n_requests=60)
        sweep = SweepSpec(name="l1xl2", base=spec, axes={
            "federation.tier1.cache_capacity": [2 * GB, 6 * GB],
            "federation.tier2.cache_capacity": [4 * GB, 12 * GB, 24 * GB],
            "federation.eviction_policy": ["lru", "fifo"],
        })
        fitted = run_sweep(sweep, batched=True, fit=True)
        tiers = spec.federation.site_tiers()
        caches = {f"{site}/cache": t for site, t in tiers.items()}
        assert set(caches.values()) == {1, 2}
        assert set(fitted.fitted_models()) == set(caches)
        for cf, cb in zip(fitted.cells, batched.cells):
            assert set(cf.models) == set(caches), cf.params
            assert cf.summary == cb.summary, cf.params
        assert fitted.solver["tier_rounds"] == 2


class TestTierSweepAxes:
    def test_tier_axis_targets_one_tier(self):
        sweep = SweepSpec(name="s", base=osdf_spec(), axes={
            "federation.tier2.cache_capacity": [7 * GB]})
        _, spec = sweep.cells()[0]
        tiers = spec.federation.site_tiers()
        for s in spec.federation.sites:
            if not s.has_cache:
                continue
            if tiers[s.name] == 2:
                assert s.cache_capacity == 7 * GB
            else:
                assert s.cache_capacity != 7 * GB

    def test_missing_tier_rejected(self):
        with pytest.raises(ValueError):
            SweepSpec(name="s", base=osdf_spec(),
                      axes={"federation.tier3.cache_capacity": [1]}).cells()

    def test_structural_tier_fields_rejected(self):
        for axis in ("federation.tier1.name", "federation.tier1.parent",
                     "federation.tier1.nope"):
            with pytest.raises(ValueError):
                SweepSpec(name="s", base=osdf_spec(),
                          axes={axis: [1]}).cells()


# ---------------------------------------------------------------------------
# Parent-tier outages stay vectorized (cache-kind events only)
# ---------------------------------------------------------------------------
class TestParentOutageParity:
    @pytest.fixture(scope="class")
    def reports(self):
        base = dataclasses.replace(
            osdf_spec(n_requests=60),
            outages=OutageSchedule.restart_storm(
                ["us-east-backbone/cache"], at=150.0, downtime=200.0,
                cold=True))
        sweep = SweepSpec(name="parent-outage", base=base, axes={
            "federation.tier1.cache_capacity": [2 * GB, 6 * GB],
            "workload.seed": [11, 12],
        })
        batched = run_sweep(sweep, batched=True)
        serial = run_sweep(sweep, batched=False, price_contention=False)
        return batched, serial

    def test_dead_parent_falls_back_flat(self, reports):
        batched, serial = reports
        assert batched.serial_cells == 0
        assert sum(c.summary["outages"] for c in batched.cells) > 0
        for cb, cs in zip(batched.cells, serial.cells):
            for k in PARITY_INTS + PARITY_DICTS:
                assert cb.summary[k] == cs.summary[k], (cb.params, k)


# ---------------------------------------------------------------------------
# Backbone-link degradation (simulated engine)
# ---------------------------------------------------------------------------
class TestLinkDegradation:
    def test_degraded_region_net_slows_transfers(self):
        spec = osdf_spec(n_requests=80, engine="sim")
        base = run_scenario(spec).summary()
        degraded = run_scenario(dataclasses.replace(
            spec, outages=OutageSchedule.link_degradation(
                ["region/us-east", "region/us-west"], at=30.0,
                duration=540.0, factor=0.02))).summary()
        # caches never fail over — the path just got slower
        assert degraded["cache_failovers"] == base["cache_failovers"]
        assert degraded["mean_seconds"] > base["mean_seconds"]

    def test_degrade_restore_idempotent(self):
        fed = build_osdf_federation()
        link = fed.topology.region_net("us-east")
        nominal = link.bandwidth
        link.degrade(0.1)
        link.degrade(0.1)  # composes against the original, not itself
        assert link.bandwidth == pytest.approx(0.1 * nominal)
        link.restore()
        assert link.bandwidth == nominal


# ---------------------------------------------------------------------------
# Batched-regime gates: what must fall back to serial replay
# ---------------------------------------------------------------------------
class TestBatchableGates:
    def _one_cell(self, base):
        return run_sweep(SweepSpec(name="gate", base=base), batched=True)

    def test_probe_ranking_serializes(self):
        rep = self._one_cell(dataclasses.replace(
            osdf_spec(n_requests=24), ranking="probe"))
        assert rep.cells[0].executor == "serial"

    def test_link_outage_serializes(self):
        rep = self._one_cell(dataclasses.replace(
            osdf_spec(n_requests=24),
            outages=OutageSchedule.link_degradation(
                ["region/us-east"], at=30.0, duration=100.0)))
        assert rep.cells[0].executor == "serial"

    def test_three_tier_hierarchy_serializes(self):
        deep = FederationSpec(sites=[
            SiteSpec(name="edge", workers=2, has_proxy=False,
                     parent="mid", cache_capacity=2 * GB),
            SiteSpec(name="mid", workers=0, has_proxy=False,
                     parent="top", cache_capacity=4 * GB),
            SiteSpec(name="top", workers=0, has_proxy=False,
                     cache_capacity=8 * GB),
            SiteSpec(name="store", workers=0, has_cache=False,
                     has_proxy=False)],
            origin_site="store")
        base = dataclasses.replace(osdf_spec(n_requests=24),
                                   federation=deep)
        rep = self._one_cell(base)
        assert rep.cells[0].executor == "serial"
        # and the serial replay still agrees with a direct run
        serial = run_scenario(base)
        assert rep.cells[0].summary["origin_egress_bytes"] == \
            serial.summary()["origin_egress_bytes"]


# ---------------------------------------------------------------------------
# Monitoring: the per-tier fleet table
# ---------------------------------------------------------------------------
class TestTierMonitoring:
    def test_tier_table_splits_levels(self):
        spec = osdf_spec(n_requests=80, engine="sim")
        fed = spec.federation.build()
        run_scenario(spec, federation=fed)
        for cache in fed.caches.values():
            cache.report_usage()
        rows = fed.monitor.tier_table()
        assert [r[0] for r in rows] == [1, 2]
        tier1, tier2 = rows
        assert tier1[1] == 4 and tier2[1] == 2  # caches per tier
        assert tier1[3] > 0   # edges pulled cache-to-cache from parents
        assert tier2[3] == 0  # backbones have no parent: origin pulls only
