"""Batched scenario sweeps: SweepSpec semantics, batched-vs-serial
parity (every cell's byte/hit/egress counters must equal a serial
``run_scenario`` of the same cell), serial fallback for cells outside
the vectorized regime, and the CI regression gate."""
import dataclasses
import json

import pytest

from repro.core import (FederationSpec, FetchRequest, ScenarioSpec,
                        SweepAggregator, SweepSpec, TierSpec, WorkloadSpec,
                        run_scenario, run_sweep)

PARITY_INTS = ("requests", "completed", "bytes_moved", "cache_hits",
               "cache_misses", "origin_egress_bytes", "evictions",
               "bytes_evicted", "admission_rejects", "cache_failovers",
               "origin_fallbacks", "group_failovers", "outages",
               "recoveries")
PARITY_FLOATS = ("hit_rate", "mean_seconds", "p50_seconds", "p95_seconds")


def base_spec(n_requests=24, **fed_kw):
    fed_kw.setdefault("num_pods", 2)
    fed_kw.setdefault("hosts_per_pod", 2)
    return ScenarioSpec(
        name="cell", engine="analytic",
        federation=FederationSpec.fleet(**fed_kw),
        workload=WorkloadSpec(kind="zipf", n_requests=n_requests,
                              working_set=8, duration=600.0, seed=5))


class TestSweepSpec:
    def test_cross_product_order_and_len(self):
        sweep = SweepSpec(name="s", base=base_spec(), axes={
            "workload.zipf_a": [0.9, 1.3],
            "workload.seed": [0, 1, 2],
        })
        assert len(sweep) == 6
        cells = sweep.cells()
        assert len(cells) == 6
        # last axis fastest
        assert [p["workload.seed"] for p, _ in cells[:3]] == [0, 1, 2]
        assert cells[0][0] == {"workload.zipf_a": 0.9, "workload.seed": 0}
        assert cells[0][1].workload.zipf_a == 0.9

    def test_axis_routing(self):
        sweep = SweepSpec(name="s", base=base_spec(), axes={
            "federation.cache_replicas": [3],
            "federation.proxy_ttl": [120.0],
            "streams": [4],
            "outage_rate": [0.5],
        })
        params, spec = sweep.cells()[0]
        cache_sites = [s for s in spec.federation.sites if s.has_cache]
        assert all(s.cache_replicas == 3 for s in cache_sites)
        assert spec.federation.proxy_ttl == 120.0
        assert spec.streams == 4
        assert spec.outages is not None and len(spec.outages) > 0
        # cold restarts at half the workload horizon
        assert all(ev.time >= 300.0 for ev in spec.outages)
        # base spec untouched (inert data)
        assert base_spec().streams == 8

    def test_unknown_axes_rejected(self):
        for axis in ("workload.nope", "federation.nope", "nope",
                     "name", "outages", "federation.name"):
            with pytest.raises(ValueError):
                SweepSpec(name="s", base=base_spec(),
                          axes={axis: [1]}).cells()

    def test_outage_axis_names_real_caches(self):
        """The outage axis must address the caches build() will create
        — one naming authority (FederationSpec.cache_names)."""
        spec = base_spec(cache_replicas=2).federation
        fed = spec.build()
        assert set(spec.cache_names()) == set(fed.caches)

    def test_cell_names_carry_params(self):
        sweep = SweepSpec(name="s", base=base_spec(),
                          axes={"workload.seed": [7]})
        _, spec = sweep.cells()[0]
        assert spec.name == "s/workload.seed=7"


class TestBatchedSerialParity:
    @pytest.fixture(scope="class")
    def reports(self):
        sweep = SweepSpec(name="parity", base=base_spec(), axes={
            "federation.cache_replicas": [1, 2],
            "workload.zipf_a": [0.9, 1.4],
            "outage_rate": [0.0, 0.5],
        })
        batched = run_sweep(sweep, batched=True)
        serial = run_sweep(sweep, batched=False, price_contention=False)
        return batched, serial

    def test_every_cell_is_byte_exact(self, reports):
        batched, serial = reports
        assert batched.batched_cells == len(batched.cells)
        for cb, cs in zip(batched.cells, serial.cells):
            assert cb.params == cs.params
            for k in PARITY_INTS:
                assert cb.summary[k] == cs.summary[k], (cb.params, k)
            for k in PARITY_FLOATS:
                assert cb.summary[k] == pytest.approx(cs.summary[k],
                                                      rel=1e-9), \
                    (cb.params, k)

    def test_outage_cells_actually_failover(self, reports):
        batched, _ = reports
        stormy = [c for c in batched.cells
                  if c.params["outage_rate"] > 0]
        assert sum(c.summary["outages"] for c in stormy) > 0
        assert any(c.summary["cache_failovers"] > 0
                   or c.summary["group_failovers"] > 0
                   or c.summary["origin_fallbacks"] > 0 for c in stormy)

    def test_pricing_gauges_present(self, reports):
        batched, _ = reports
        assert batched.solver["solve_calls"] >= 1
        assert batched.solver["priced_cells"] == len(batched.cells)
        for c in batched.cells:
            assert c.pricing["peak_flows"] > 0
            assert c.pricing["storm_finish_seconds"] > 0

    def test_single_cell_sweep(self):
        """Batch-of-one: a sweep with no axes still runs (and prices)."""
        sweep = SweepSpec(name="one", base=base_spec(n_requests=8))
        rep = run_sweep(sweep, batched=True)
        assert len(rep.cells) == 1
        assert rep.cells[0].executor == "batched"
        serial = run_scenario(sweep.cells()[0][1])
        for k in ("bytes_moved", "cache_hits", "cache_misses",
                  "origin_egress_bytes"):
            assert rep.cells[0].summary[k] == serial.summary()[k]

    def test_direct_method_cells(self):
        sweep = SweepSpec(name="direct",
                          base=dataclasses.replace(base_spec(n_requests=10),
                                                   method="direct"),
                          axes={"workload.seed": [0, 1]})
        b = run_sweep(sweep, batched=True)
        s = run_sweep(sweep, batched=False, price_contention=False)
        assert b.batched_cells == 2
        for cb, cs in zip(b.cells, s.cells):
            for k in ("bytes_moved", "origin_egress_bytes", "cache_hits"):
                assert cb.summary[k] == cs.summary[k]
            assert cb.summary["cache_hits"] == 0  # direct bypasses caches

    def test_explicit_request_workload(self):
        reqs = [FetchRequest(path=f"/d/obj{i % 3}", site="pod0",
                             worker=i % 2, at=float(i), size=int(5e7))
                for i in range(12)]
        sweep = SweepSpec(
            name="explicit",
            base=dataclasses.replace(base_spec(), workload=reqs))
        b = run_sweep(sweep, batched=True)
        s = run_sweep(sweep, batched=False, price_contention=False)
        assert b.cells[0].executor == "batched"
        for k in PARITY_INTS:
            assert b.cells[0].summary[k] == s.cells[0].summary[k], k

    def test_not_found_requests_under_outage_stay_exact(self):
        """Unpublished (size-0) paths still walk the ranked chain on
        the serial plane, so their group-failover accounting must
        survive an outage on the batched path too."""
        # horizon = max(at) + 60 = 140 -> cold restart at t=70 for 35 s:
        # the t=70/t=80 requests run while every cache is down
        times = (0.0, 20.0, 70.0, 80.0)
        reqs = [FetchRequest(path="/d/real", site="pod0", at=t,
                             size=int(5e7)) for t in times]
        reqs += [FetchRequest(path="/d/ghost", site="pod0", at=t,
                              size=0) for t in times]
        sweep = SweepSpec(
            name="ghost",
            base=dataclasses.replace(base_spec(num_pods=1), workload=reqs),
            axes={"outage_rate": [1.0]})
        b = run_sweep(sweep, batched=True, price_contention=False)
        s = run_sweep(sweep, batched=False, price_contention=False)
        assert b.cells[0].executor == "batched"
        for k in PARITY_INTS:
            assert b.cells[0].summary[k] == s.cells[0].summary[k], k
        assert b.cells[0].summary["group_failovers"] > 0


class TestSerialFallback:
    def test_sim_engine_cells_fall_back(self):
        sweep = SweepSpec(name="mixed", base=base_spec(n_requests=6),
                          axes={"engine": ["analytic", "sim"]})
        rep = run_sweep(sweep, batched=True)
        by_engine = {c.params["engine"]: c for c in rep.cells}
        assert by_engine["analytic"].executor == "batched"
        assert by_engine["sim"].executor == "serial"
        assert rep.serial_cells == 1 and rep.batched_cells == 1
        # engine parity on byte counters holds across the two cells
        for k in ("bytes_moved", "cache_hits", "cache_misses",
                  "origin_egress_bytes"):
            assert (by_engine["analytic"].summary[k]
                    == by_engine["sim"].summary[k]), k

    def test_proxy_method_falls_back(self):
        sweep = SweepSpec(
            name="proxy",
            base=dataclasses.replace(base_spec(n_requests=6),
                                     method="proxy"))
        rep = run_sweep(sweep, batched=True)
        assert rep.cells[0].executor == "serial"

    def test_lfu_and_ttl_policies_fall_back(self):
        """Victim orders the kernels don't model (LFU frequency buckets,
        TTL expiry) still run serially — with identical semantics."""
        sweep = SweepSpec(name="pol", base=base_spec(n_requests=8),
                          axes={"federation.eviction_policy":
                                ["lru", "fifo", "lfu", "ttl"]})
        rep = run_sweep(sweep, batched=True)
        by_policy = {c.params["federation.eviction_policy"]: c.executor
                     for c in rep.cells}
        assert by_policy == {"lru": "batched", "fifo": "batched",
                             "lfu": "serial", "ttl": "serial"}

    def test_policy_instance_axis_falls_back(self):
        """A non-string eviction_policy (a policy *instance*) cannot be
        introspected by the kernels; the cell must go serial."""
        from repro.core import LRUPolicy
        sweep = SweepSpec(name="inst", base=base_spec(n_requests=6),
                          axes={"federation.eviction_policy":
                                [LRUPolicy()]})
        rep = run_sweep(sweep, batched=True)
        assert rep.cells[0].executor == "serial"

    def test_control_plane_cells_fall_back_and_are_counted(self):
        """Admission queues / breakers are stateful across requests in
        ways the hit/miss kernels don't model: a cell with a
        ControlPlaneSpec must be classified serial (and counted as
        such), while its control-free sibling stays batched with
        byte-exact parity against a serial run."""
        from repro.core import ControlPlaneSpec
        base = base_spec(n_requests=12)
        base = dataclasses.replace(
            base, workload=dataclasses.replace(base.workload, duration=2.0))
        sweep = SweepSpec(name="ctrl", base=base,
                          axes={"control": [None, ControlPlaneSpec(
                              max_concurrent=1, queue_depth=1)]})
        rep = run_sweep(sweep, batched=True)
        by_ctrl = {c.params["control"] is not None: c for c in rep.cells}
        assert by_ctrl[False].executor == "batched"
        assert by_ctrl[True].executor == "serial"
        assert rep.serial_cells == 1 and rep.batched_cells == 1
        # the control-free cell is bit-identical to a serial run of the
        # same spec: attaching control elsewhere must not perturb it
        serial = run_scenario(base).summary()
        for k in PARITY_INTS:
            assert by_ctrl[False].summary[k] == serial[k], k
        # the control cell actually exercised the queue
        assert by_ctrl[True].summary["sheds"] + \
            by_ctrl[True].summary["queue_waits"] > 0

    def test_control_free_sweep_has_zero_serial_cells(self):
        """Acceptance guard: adding the control axis must not push
        ordinary sweeps off the batched path."""
        sweep = SweepSpec(name="plain", base=base_spec(n_requests=12),
                          axes={"workload.seed": [0, 1, 2]})
        rep = run_sweep(sweep, batched=True)
        assert rep.serial_cells == 0
        assert rep.batched_cells == 3


class TestEvictionParity:
    """The regime PR 5 closes: capacity / policy / admission axes run
    batched (stack-distance + state-machine kernels) with cell-exact
    counters — including evictions, bytes_evicted and re-pull egress."""

    @pytest.fixture(scope="class")
    def reports(self):
        # working set (~8 objects × ~hundreds of MB) far exceeds the
        # smallest capacities → heavy eviction churn in half the cells
        sweep = SweepSpec(name="evict", base=base_spec(n_requests=40), axes={
            "federation.cache_capacity": [2e8, 5e8, 1e9, 32e12],
            "federation.eviction_policy": ["lru", "fifo"],
            "federation.admission_max_fraction": [1.0, 0.3],
        })
        batched = run_sweep(sweep, batched=True)
        serial = run_sweep(sweep, batched=False, price_contention=False)
        return batched, serial

    def test_acceptance_no_serial_cells(self, reports):
        """ISSUE-5 acceptance: the capacity × {lru,fifo} × admission
        sweep runs wholly through the batched executor."""
        batched, _ = reports
        assert batched.serial_cells == 0
        assert batched.batched_cells == len(batched.cells) == 16

    def test_every_cell_is_byte_exact(self, reports):
        batched, serial = reports
        for cb, cs in zip(batched.cells, serial.cells):
            assert cb.params == cs.params
            for k in PARITY_INTS:
                assert cb.summary[k] == cs.summary[k], (cb.params, k)
            for k in PARITY_FLOATS:
                assert cb.summary[k] == pytest.approx(cs.summary[k],
                                                      rel=1e-9), \
                    (cb.params, k)

    def test_evictions_actually_happen_and_drive_egress(self, reports):
        batched, _ = reports
        tiny = [c for c in batched.cells
                if c.params["federation.cache_capacity"] == 2e8
                and c.params["federation.admission_max_fraction"] == 1.0]
        huge = [c for c in batched.cells
                if c.params["federation.cache_capacity"] == 32e12
                and c.params["federation.admission_max_fraction"] == 1.0]
        assert all(c.summary["evictions"] > 0 for c in tiny)
        assert all(c.summary["bytes_evicted"] > 0 for c in tiny)
        assert all(c.summary["evictions"] == 0 for c in huge)
        # re-pulls of evicted chunks show up as extra origin egress
        for t, h in zip(tiny, huge):
            assert (t.summary["origin_egress_bytes"]
                    > h.summary["origin_egress_bytes"])

    def test_kernel_telemetry_accounts_every_call(self, reports):
        """Each hit/miss kernel reports one padded bucket per jitted
        call, and its batches hold exactly the problems plus the
        all-dummy filler."""
        batched, _ = reports
        s = batched.solver
        for kind in ("stack", "fifo"):
            buckets = s[f"{kind}_buckets"]
            assert s[f"{kind}_calls"] == len(buckets) >= 1, kind
            assert sum(b[0] for b in buckets) \
                == s[f"{kind}_problems"] + s[f"{kind}_padded"], kind
        assert 0 < s["min_stream_refs"] <= s["max_stream_refs"]

    def test_kernel_telemetry_is_pinned(self):
        """The exact batches of a sweep that reaches all four batched
        kernels in both resolution rounds: two pods filling from a hub
        cache, under outages (a non-head pod serving before the meta is
        located sends LRU with admission to the state machine), priced.
        The values were read from a run of the executor before its
        rounds and its kernels' bucket loops were merged into one
        ``_Round`` type and one batch runner; they pin the padding
        policy (``padded_share.sweep``) through any such refactor."""
        fed = FederationSpec.fleet(num_pods=2, hosts_per_pod=2)
        pods = [s for s in fed.sites if s.has_cache]
        hub = dataclasses.replace(pods[0], name="hub", workers=0)
        fed = dataclasses.replace(fed, sites=(
            TierSpec(parent="hub", sites=pods).flatten() + [hub]
            + [s for s in fed.sites if not s.has_cache]))
        base = dataclasses.replace(base_spec(n_requests=40), federation=fed)
        sweep = SweepSpec(name="pinned", base=base, axes={
            "federation.cache_capacity": [5e8, 1e9, 2e9],
            "federation.eviction_policy": ["lru", "fifo"],
            "federation.admission_max_fraction": [1.0, 0.25],
            "outage_rate": [0.5],
        })
        s = run_sweep(sweep, batched=True).solver
        got = {k: s.get(k) for k in (
            "stack_calls", "stack_problems", "stack_padded",
            "stack_buckets", "fifo_calls", "fifo_problems", "fifo_padded",
            "fifo_buckets", "cache_sim_calls", "cache_sim_problems",
            "cache_sim_padded", "cache_sim_buckets", "solve_calls",
            "problems", "padded_problems", "buckets", "tier_rounds")}
        assert got == {
            "stack_calls": 2, "stack_problems": 8, "stack_padded": 4,
            "stack_buckets": [(8, 256), (4, 512)],
            "fifo_calls": 3, "fifo_problems": 18, "fifo_padded": 6,
            "fifo_buckets": [(8, 512, 256), (8, 512, 512),
                             (8, 2048, 1024)],
            "cache_sim_calls": 1, "cache_sim_problems": 6,
            "cache_sim_padded": 2, "cache_sim_buckets": [(8, 2048, 1024)],
            "solve_calls": 1, "problems": 12, "padded_problems": 4,
            "buckets": [(16, 128, 16, 8)], "tier_rounds": 2,
        }

    def test_admission_rejects_are_counted(self, reports):
        batched, _ = reports
        filtered = [c for c in batched.cells
                    if c.params["federation.admission_max_fraction"] < 1.0
                    and c.params["federation.cache_capacity"] <= 1e9]
        assert any(c.summary["admission_rejects"] > 0 for c in filtered)

    def test_eviction_cells_under_outage_stay_exact(self):
        """Cold restarts interleave with eviction churn: segment-aware
        distances and state-machine resets must both stay exact."""
        sweep = SweepSpec(name="stormy", base=base_spec(n_requests=30),
                          axes={
                              "federation.cache_capacity": [4e8],
                              "federation.eviction_policy": ["lru", "fifo"],
                              "outage_rate": [0.5],
                          })
        b = run_sweep(sweep, batched=True, price_contention=False)
        s = run_sweep(sweep, batched=False, price_contention=False)
        assert b.serial_cells == 0
        for cb, cs in zip(b.cells, s.cells):
            for k in PARITY_INTS:
                assert cb.summary[k] == cs.summary[k], (cb.params, k)
            assert cb.summary["evictions"] > 0

    def test_single_evicting_cell_matches_run_scenario(self):
        """Straight against run_scenario, not just the serial sweep."""
        sweep = SweepSpec(name="tiny", base=base_spec(n_requests=20),
                          axes={"federation.cache_capacity": [5e8]})
        rep = run_sweep(sweep, batched=True)
        assert rep.cells[0].executor == "batched"
        serial = run_scenario(sweep.cells()[0][1]).summary()
        for k in PARITY_INTS:
            assert rep.cells[0].summary[k] == serial[k], k

    def test_policy_marginals_surface(self):
        """SweepAggregator groups the eviction axis for dashboards."""
        from repro.core import SweepAggregator
        sweep = SweepSpec(name="pm", base=base_spec(n_requests=16), axes={
            "federation.eviction_policy": ["lru", "fifo"],
            "federation.cache_capacity": [3e8, 1e12],
        })
        rep = run_sweep(sweep, batched=True, price_contention=False)
        agg = SweepAggregator()
        for cell in rep.cells:
            agg.add(cell.params, cell.summary)
        rows = agg.policy_marginals()
        assert {r[0] for r in rows} == {"lru", "fifo"}
        by_policy = {r[0]: r for r in rows}
        # (policy, cells, hit_rate, evictions, bytes_evicted, rejects)
        assert by_policy["lru"][1] == 2
        assert by_policy["lru"][3] > 0   # mean evictions over the column


class TestSweepAggregator:
    def test_marginals(self):
        agg = SweepAggregator()
        for a in (1, 2):
            for b in (10, 20):
                agg.add({"a": a, "b": b},
                        {"hit_rate": 0.1 * a + 0.001 * b})
        assert len(agg) == 4
        assert agg.axes() == {"a": [1, 2], "b": [10, 20]}
        rows = agg.marginal("a", "hit_rate")
        assert rows[0][0] == 1 and rows[0][1] == 2
        assert rows[0][2] == pytest.approx(0.1 + 0.015)
        assert rows[1][2] == pytest.approx(0.2 + 0.015)
        table = agg.table("hit_rate")
        assert {r[0] for r in table} == {"a", "b"}

    def test_report_marginal(self):
        sweep = SweepSpec(name="m", base=base_spec(n_requests=8),
                          axes={"workload.zipf_a": [0.8, 1.6]})
        rep = run_sweep(sweep, batched=True, price_contention=False)
        rows = rep.marginal("workload.zipf_a", "hit_rate")
        assert [v for v, _ in rows] == [0.8, 1.6]


class TestRegressionGate:
    @pytest.fixture()
    def baseline(self):
        from benchmarks.check_regression import BASELINE
        return json.loads(BASELINE.read_text())

    def test_committed_baseline_passes_on_itself(self, baseline):
        from benchmarks.check_regression import compare
        current = {name: float(spec["value"])
                   for name, spec in baseline["metrics"].items()}
        failures, rows = compare(baseline, current)
        assert failures == []
        assert all(r[-1] == "ok" for r in rows)

    def test_two_x_slowdown_fails(self, baseline):
        from benchmarks.check_regression import compare, format_table
        current = {}
        for name, spec in baseline["metrics"].items():
            v = float(spec["value"])
            current[name] = (v / 2 if spec.get("direction", "min") == "min"
                             else v * 2 + 1)
        failures, rows = compare(baseline, current)
        assert any("sweep_speedup" in f for f in failures)
        # every 'min' metric halved must regress (25% tolerance < 50%)
        regressed = {r[0] for r in rows if r[-1] == "REGRESSED"}
        assert "sweep_speedup" in regressed
        assert "storm_coalescing_ratio" in regressed
        # the diff is readable: metric name + verdict in the table
        table = format_table(rows)
        assert "sweep_speedup" in table and "REGRESSED" in table

    def test_missing_artifact_fails(self, baseline):
        from benchmarks.check_regression import compare
        failures, rows = compare(baseline, {})
        assert len(failures) == len(baseline["metrics"])
        assert all(r[-1] == "MISSING" for r in rows)

    def test_update_refuses_partial_baselines(self, baseline, tmp_path):
        """--update with missing artifacts must not silently keep stale
        values for the unrefreshed metrics."""
        from benchmarks.check_regression import update_baseline
        path = tmp_path / "baseline.json"
        path.write_text(json.dumps(baseline))
        partial = {"sweep_speedup": 9.0}   # everything else missing
        missing = update_baseline(json.loads(path.read_text()), partial,
                                  path)
        assert "storm_coalescing_ratio" in missing
        # nothing was written
        assert json.loads(path.read_text()) == baseline
        full = {name: float(spec["value"]) + 1
                for name, spec in baseline["metrics"].items()}
        assert update_baseline(json.loads(path.read_text()), full,
                               path) == []
        updated = json.loads(path.read_text())
        assert all(updated["metrics"][n]["value"] == v
                   for n, v in full.items())

    def test_speedup_floor_is_enforced(self, baseline):
        """The ISSUE-4 acceptance floor: even a baseline drift cannot
        let the batched path fall under 3x."""
        from benchmarks.check_regression import compare
        spec = baseline["metrics"]["sweep_speedup"]
        assert float(spec.get("floor", 0)) >= 3.0
        current = {"sweep_speedup": 2.9}
        failures, _ = compare({"metrics": {"sweep_speedup": spec}}, current)
        assert failures


class TestRunHarnessArtifactHygiene:
    def test_failed_bench_discards_its_artifacts(self, tmp_path):
        import benchmarks.run as harness

        class FakeBench:
            ARTIFACT_FILES = ("__stale_test__.json",)

        stale = (harness.Path(harness.__file__).parent / "artifacts"
                 / "__stale_test__.json")
        stale.parent.mkdir(exist_ok=True, parents=True)
        stale.write_text("{}")
        try:
            removed = harness.discard_artifacts(FakeBench())
            assert removed == ["__stale_test__.json"]
            assert not stale.exists()
            # idempotent: nothing left to remove
            assert harness.discard_artifacts(FakeBench()) == []
        finally:
            if stale.exists():
                stale.unlink()

    def test_every_artifact_writer_declares_ownership(self):
        """Each bench that writes artifacts must declare ARTIFACT_FILES
        so the harness can discard stale JSON when it fails."""
        import benchmarks.run as harness
        for name, mod in harness.discover().items():
            src = open(mod.__file__).read()
            if "write_text" in src and "artifacts" in src.lower():
                assert getattr(mod, "ARTIFACT_FILES", None), \
                    f"{name} writes artifacts but declares no " \
                    f"ARTIFACT_FILES"
