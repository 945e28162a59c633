"""The metric arithmetic: rates over whole queries and the whole window,
host time outside the kernel spans, padding shares, which cell reports
which metric."""
import json

import pytest

from run import ROOT, Run, cell_metrics, load_reader

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def made_up_run():
    """Three 16-cell queries; the third starts after a 3 s stall."""
    run = Run()
    run.queries = [
        {"start": 10.0, "end": 11.0, "cells": 16, "solver": {}},
        {"start": 11.0, "end": 12.0, "cells": 16, "solver": {}},
        {"start": 15.0, "end": 16.0, "cells": 16, "solver": {}},
    ]
    run.spans = [("fifo_sim_batch", 10.2, 10.6),
                 ("stack_distances_batch", 11.5, 11.75),
                 ("fifo_sim_batch", 12.5, 14.0),      # in the stall
                 ("maxmin_rates_batch", 15.9, 16.4)]  # past the last end
    return run


def test_rate_counts_whole_queries_over_the_whole_window():
    run = made_up_run()
    rate = load_reader("sweep_cells_per_s")(run)
    # 48 cells from the first start (10 s) to the last end (16 s): the
    # stall counts against the rate, which a mean of per-query rates
    # (16 cells/s) would hide
    assert rate == pytest.approx(48 / 6.0)
    assert load_reader("sweep_cells_per_s")(Run()) is None


def test_host_seconds_subtract_the_kernel_spans_inside_each_query():
    run = made_up_run()
    # query walls 3 s; spans inside queries 0.4 + 0.25 + 0.1 s
    assert load_reader("host_s.sweep")(run) == pytest.approx(
        (3.0 - 0.75) / 3)
    run.spans = []
    assert load_reader("host_s.sweep")(run) is None


def test_padded_share_counts_filler_over_all_slots():
    run = made_up_run()
    run.queries[-1]["solver"] = {"stack_problems": 20, "stack_padded": 4,
                                 "fifo_problems": 40, "fifo_padded": 8,
                                 "problems": 16, "padded_problems": 0}
    assert load_reader("padded_share.sweep")(run) == pytest.approx(
        100 * 12 / 88)
    run.queries[-1]["solver"] = {}
    assert load_reader("padded_share.sweep")(run) is None


def test_setup_and_trace_readers():
    run = made_up_run()
    run.setup_s = 12.5
    assert load_reader("setup_s")(run) == 12.5
    assert load_reader("idle_share.sweep")(run) is None
    assert load_reader("dev_s.fifo")(run) is None
    run.trace = {"busy_s": 1.5, "window_s": 6.0, "queries": 3,
                 "module_s": {"jit__fifo_replay(7)": 0.9}}
    assert load_reader("idle_share.sweep")(run) == pytest.approx(75.0)
    assert load_reader("dev_s.fifo")(run) == pytest.approx(0.3)
    assert load_reader("dev_s.cache_sim")(run) is None


def test_each_cell_reports_the_metrics_that_name_it():
    outage = {m["name"] for m in cell_metrics(
        BENCH, "osg-sweep-admit-outage", trace=True)}
    capacity = {m["name"] for m in cell_metrics(
        BENCH, "osg-day-sweep-capacity", trace=True)}
    assert "dev_s.cache_sim" in outage
    assert "dev_s.cache_sim" not in capacity
    assert capacity <= outage
    assert {m["name"] for m in cell_metrics(
        BENCH, "osg-day-sweep-capacity", trace=False)} == {
            "sweep_cells_per_s", "setup_s"}
