"""The reduction from trace to device numbers, on traces recorded on one
TPU v5 lite (``data/trace-<cell>.json``: the plain lists that
``bench.trace.load`` reads out of the profiler's file; the day cell's
with the per-op trace off, so with module events alone)."""
import json

import pytest

from bench import trace
from run import HERE, Run, load_reader

# Expected readings of the recorded traces: (busy_s, window_s, queries,
# {metric: value}).
RECORDED = {
    "osg-day-sweep-capacity": (6.653247404, 10.335328668, 1, {
        "dev_s.distances": 0.865665651,
        "dev_s.fifo": 5.4152817440000005,
        "dev_s.waterfill.sweep": 0.37230000900000004,
        "idle_share.sweep": 35.626165188150935,
    }),
    "osg-sweep-admit-outage": (3.708961184, 3.9235004370000004, 1, {
        "dev_s.cache_sim": 3.512937931,
        "dev_s.distances": 0.019087251,
        "dev_s.fifo": 0.153967076,
        "dev_s.waterfill.sweep": 0.022968926,
        "idle_share.sweep": 5.468057323935005,
    }),
    "osg-sweep-capacity": (0.851442762, 2.14579893, 9, {
        "dev_s.distances": 0.085083562 / 9,
        "dev_s.fifo": 0.5596471000000001 / 9,
        "dev_s.waterfill.sweep": 0.2067121 / 9,
        "idle_share.sweep": 60.32047783712895,
    }),
}


@pytest.mark.parametrize("cell", sorted(RECORDED))
def test_recorded_trace_reduces_to_fixed_values(cell):
    events = json.loads((HERE / "data" / f"trace-{cell}.json").read_text())
    busy, window, queries, metrics = RECORDED[cell]
    reduced = trace.reduce(events)
    assert reduced["busy_s"] == pytest.approx(busy, rel=1e-9)
    assert reduced["window_s"] == pytest.approx(window, rel=1e-9)
    assert reduced["queries"] == queries
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    assert len(reduced["device_ops"]) <= 10
    assert len(reduced["idle_gaps"]) <= 10
    run = Run()
    run.trace = reduced
    for name, value in metrics.items():
        assert load_reader(name)(run) == pytest.approx(value, rel=1e-6)
    if "dev_s.cache_sim" not in metrics:
        assert load_reader("dev_s.cache_sim")(run) is None


def test_busy_time_is_the_union_of_module_intervals():
    events = {"devices": {"/device:TPU:0": {
        "modules": [["jit__fifo_replay(1)", 100.0, 50.0],
                    ["jit__distances(2)", 120.0, 80.0],    # overlaps
                    ["jit__fifo_replay(1)", 400.0, 100.0],
                    ["jit__fifo_replay(1)", 900.0, 10.0]],  # outside
        "ops": {"%while.1 while": 2.0, "%fusion.2 fusion": 3.0}}},
        "host": [["chipbench.query", 50.0, 300.0],
                 ["chipbench.query", 380.0, 170.0],
                 ["fifo_sim_batch", 390.0, 150.0]]}
    r = trace.reduce(events)
    assert r["window_s"] == pytest.approx(500e-9)
    assert r["busy_s"] == pytest.approx(200e-9)
    assert r["queries"] == 2
    assert r["device_ops"] == [["%fusion.2 fusion", 3.0],
                               ["%while.1 while", 2.0]]
    # gaps: 50-100 and 200-400 in the host path, 500-550 in a kernel
    # entry's span
    assert [g[0] for g in r["idle_gaps"]] == [
        "sweep host path", "sweep host path", "fifo_sim_batch"]
    assert trace.reduce({"devices": {}, "host": []}) is None


def test_op_names_keep_name_and_opcode():
    assert trace.op_name("%while.22 = (u32[]{:T(128)}, f32[4]{0:T(128)S(1)})"
                         " while((u32[]{:T(128)}) %tuple.3)") == \
        "%while.22 while"
    assert trace.op_name("%fusion.82 = pred[16384]{0:T(1024)(128)(4,1)S(1)}"
                         " fusion(s32[16384]{0:T(1024)S(1)} %g)") == \
        "%fusion.82 fusion"


def test_modules_stand_in_for_ops_in_a_trace_without_op_events():
    events = {"devices": {"/device:TPU:0": {
        "modules": [["jit__fifo_replay(1)", 100.0, 50.0],
                    ["jit_solve_waterfill(3)", 200.0, 20.0],
                    ["jit__fifo_replay(1)", 300.0, 40.0]],
        "ops": {}}},
        "host": [["chipbench.query", 50.0, 300.0]]}
    r = trace.reduce(events)
    assert r["device_ops"] == [["jit__fifo_replay(1)", pytest.approx(90e-9)],
                               ["jit_solve_waterfill(3)",
                                pytest.approx(20e-9)]]


def test_traced_runs_keep_the_environments_libtpu_args():
    assert trace.libtpu_args("") == trace.HLO_TRACE_OFF
    assert trace.libtpu_args("--xla_tpu_foo=1") == \
        "--xla_tpu_foo=1 " + trace.HLO_TRACE_OFF
