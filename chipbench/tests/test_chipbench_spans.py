"""The program's own spans in a traced run (``bench/spans.py``): self
times, idle time no stage names, the readers built on them, and the
guarantee that adding the spans changes nothing ``bench.trace`` read."""
import json

import pytest

from bench import spans, trace
from run import HERE, Run, load_reader

NEW = ("host_s.sweep.route", "host_s.sweep.finalize", "host_s.kernel_io",
       "host_s.sweep.unsplit", "idle_unattributed_share.sweep")
OLD = ("dev_s.cache_sim", "dev_s.distances", "dev_s.fifo",
       "dev_s.waterfill.sweep", "idle_share.sweep")


def made_up_events():
    """One query (0 to 1000 ns): a route with its two stages, a FIFO
    kernel call with its bucket's pack, device and unpack, a finalize;
    the device busy from 460 to 640 ns."""
    return {"devices": {"/device:TPU:0": {
        "modules": [["jit__fifo_replay(1)", 460.0, 180.0]], "ops": {}}},
        "host": [["chipbench.query", 0.0, 1000.0],
                 ["fifo_sim_batch", 395.0, 310.0],
                 ["repro.sweep", 10.0, 980.0],
                 ["repro.sweep.route", 10.0, 290.0],
                 ["repro.sweep.route.streams", 20.0, 180.0],
                 ["repro.sweep.route.flows", 200.0, 90.0],
                 ["repro.kernel.fifo", 400.0, 300.0],
                 ["repro.kernel.fifo.pack", 400.0, 50.0],
                 ["repro.kernel.fifo.device", 450.0, 200.0],
                 ["repro.kernel.fifo.unpack", 650.0, 40.0],
                 ["repro.sweep.finalize", 700.0, 200.0]]}


def without_program_spans(events):
    return {"devices": events["devices"],
            "host": [h for h in events["host"]
                     if not h[0].startswith(spans.PREFIX)]}


def test_self_time_is_the_span_less_its_nested_spans():
    r = spans.reduce(made_up_events())
    assert r["span_s"]["repro.sweep"] == pytest.approx(980e-9)
    assert r["span_self_s"] == pytest.approx({
        "repro.sweep": (980 - 290 - 300 - 200) * 1e-9,
        "repro.sweep.route": (290 - 180 - 90) * 1e-9,
        "repro.sweep.route.streams": 180e-9,
        "repro.sweep.route.flows": 90e-9,
        "repro.kernel.fifo": (300 - 50 - 200 - 40) * 1e-9,
        "repro.kernel.fifo.pack": 50e-9,
        "repro.kernel.fifo.device": 200e-9,
        "repro.kernel.fifo.unpack": 40e-9,
        "repro.sweep.finalize": 200e-9})


def test_idle_time_no_stage_names_is_unattributed():
    r = spans.reduce(made_up_events())
    # idle 0-460 and 640-1000 ns; stages cover 10-300 and 400-900. Left:
    # 0-10 (before the sweep), 300-400 (under the root alone), 900-1000
    # (after the last stage); 640-900, under the unpack and the
    # finalize, is named
    assert r["idle_unattributed_s"] == pytest.approx(210e-9)
    assert r["window_s"] - r["busy_s"] == pytest.approx(820e-9)
    run = Run()
    run.trace = trace.reduce(made_up_events())
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(spans, "of_run", lambda _: r)
        assert load_reader("idle_unattributed_share.sweep")(run) == \
            pytest.approx(100 * 210 / 820)
    # the gaps are named by the innermost span, now the program's
    assert {name for name, _ in r["idle_gaps"]} == {
        "repro.sweep.route.flows", "repro.sweep.finalize"}


def test_program_spans_leave_every_old_reading_as_it_was():
    events = made_up_events()
    with_spans = trace.reduce(events)
    plain = trace.reduce(without_program_spans(events))
    for key in ("busy_s", "window_s", "module_s", "queries", "device_ops"):
        assert with_spans[key] == plain[key], key
    assert [g[1] for g in with_spans["idle_gaps"]] == \
        [g[1] for g in plain["idle_gaps"]]


def test_no_root_span_reads_nothing():
    """A program that writes no ``repro.sweep`` span (the parent of
    these spans) gives no reduction, and the readers report nothing."""
    events = without_program_spans(made_up_events())
    assert spans.reduce(events) is None
    run = Run()
    run.trace = trace.reduce(events)
    run.queries = [{"start": 0.0, "end": 1.0, "cells": 16, "solver": {}}]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(spans, "of_run", lambda _: None)
        for name in NEW:
            assert load_reader(name)(run) is None, name
    assert spans.of_run(Run()) is None


def test_a_run_finds_its_own_cells_trace(tmp_path):
    """Two cells' traces on disk: the run reads the one whose recorded
    events reduce to its own trace, with that directory's spans."""
    mine = made_up_events()
    other = made_up_events()
    other["devices"]["/device:TPU:0"]["modules"][0][2] = 100.0
    for cell, events in (("mine", mine), ("other", other)):
        (tmp_path / cell).mkdir()
        (tmp_path / cell / "events.json").write_text(
            json.dumps(without_program_spans(events)))
    read_from = []

    def program_spans(trace_dir):
        read_from.append(trace_dir)
        return [h for h in mine["host"] if h[0].startswith(spans.PREFIX)]
    run = Run()
    run.trace = trace.reduce(without_program_spans(mine))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(spans, "TRACE_ROOT", tmp_path)
        mp.setattr(spans, "program_spans", program_spans)
        got = spans.of_run(run)
    assert read_from == [str(tmp_path / "mine")]
    assert got["idle_unattributed_s"] == pytest.approx(210e-9)


# A traced day query recorded on one TPU v5 lite with the program's spans
# (module events only, as ``bench/spans.py`` reads them), and its
# readings, which the run's own result line printed too.
SPANS_TRACE = "trace-osg-day-sweep-capacity-spans.json"
RECORDED = {
    "host_s.sweep.route": 1.437260896,
    "host_s.sweep.finalize": 1.2162891840000003,
    "host_s.kernel_io": 0.6528208369999999,
    "host_s.sweep.unsplit": 0.080283221,
    "idle_unattributed_share.sweep": 2.1917134234049804,
}


def recorded():
    return json.loads((HERE / "data" / SPANS_TRACE).read_text())


def read_recorded(name, events):
    run = Run()
    run.trace = trace.reduce(events)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(spans, "of_run", lambda _: spans.reduce(events))
        return load_reader(name)(run)


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_spans_trace_reads_fixed_values(name):
    assert read_recorded(name, recorded()) == \
        pytest.approx(RECORDED[name], rel=1e-9)


@pytest.mark.parametrize("name", OLD)
def test_old_readers_read_the_same_without_program_spans(name):
    events = recorded()
    assert read_recorded(name, events) == \
        read_recorded(name, without_program_spans(events))


def test_recorded_stages_add_up_to_the_sweep_span():
    """Route, finalize, kernel I/O, what no stage splits and the device
    calls partition the day query's ``repro.sweep`` span."""
    events = recorded()
    r = spans.reduce(events)
    device = sum(v for k, v in r["span_s"].items()
                 if k.startswith("repro.kernel.") and k.endswith(".device"))
    stages = sum(read_recorded(name, events) for name in NEW[:4])
    assert stages + device == pytest.approx(r["span_s"]["repro.sweep"],
                                            rel=1e-9)
    assert {name for name, _ in r["idle_gaps"]} <= set(r["span_s"])
