"""The comparison that decides ``correct``, at a size a test run holds.

A sound run through the harness (with its look for a chip skipped) comes
out correct; the control (the reference itself with every byte count in
float32 and the pricing in bfloat16) and each fault a sweep cell can
have, planted under the timed path, come out not correct.  The serial
``CacheServer`` replay is a second witness that the reference says what
the program means, and the program's scalar max-min solver one that the
pricing reference does.
"""
import time

import numpy as np
import pytest

import run
from bench import checks, pricing, reference

CELLS = ("osg-sweep-admit-outage", "osg-day-sweep-capacity")
DEVICE = {"platform": "cpu", "kind": "test", "count": 1}
SEED = 2**31 + 12345
SWEEP = run.load_kind("sweep")


def small(cell, requests=40):
    spec = run.load_cell(cell)
    spec["deployment"]["trace"]["requests"] = requests
    return spec


def measure(spec):
    # a window of 0 s answers exactly one query after the warm-up
    return run.measure(spec, SEED, 0.0, False, DEVICE, time.monotonic())


def answer_altered(name, orig):
    """The first answer has one value altered where it is produced: a
    FIFO hit bit flipped, or a storm's rate raised by a tenth."""
    def fault(problems, stats=None):
        out = orig(problems, stats=stats)
        if name == "maxmin_rates_batch":
            rates = out[0].copy()
            rates[0] *= 1.1
            return [rates] + out[1:]
        hits, ev, evb = out[0]
        hits = hits.copy()
        hits[0] = ~hits[0]
        return [(hits, ev, evb)] + out[1:]
    return fault


def half_left_out(name, orig):
    """Only the first half of the batch is solved; the other half is
    answered as if nothing had ever been resident, or as if its flows
    all ran at the first half's mean rate."""
    def fault(problems, stats=None):
        half = len(problems) // 2
        out = orig(problems[:half], stats=stats)
        if name == "maxmin_rates_batch":
            mean = float(np.mean(np.concatenate(out)))
            return out + [np.full(len(p[1]), mean) for p in problems[half:]]
        return out + [(np.zeros(len(p[0]), bool), 0, 0)
                      for p in problems[half:]]
    return fault


def state_unchanged(name, orig):
    """The stack-distance scan never updates its state: every reference
    finds the stack empty and misses."""
    def fault(problems, stats=None):
        orig(problems, stats=stats)
        return [np.full(len(prev), np.inf) for prev, _ in problems]
    return fault


FIFO = ("repro.kernels.stack_distance", "fifo_sim_batch")
PRICING = ("repro.kernels.batched_maxmin", "maxmin_rates_batch")
FAULTS = {
    "answer_altered": (FIFO, answer_altered),
    "half_left_out": (FIFO, half_left_out),
    "state_unchanged": (("repro.kernels.stack_distance",
                         "stack_distances_batch"), state_unchanged),
    "pricing_answer_altered": (PRICING, answer_altered),
    "pricing_half_left_out": (PRICING, half_left_out),
}


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    result = measure(small(cell))
    assert result["correct"], result["compared"]
    assert result["attempted"] == 16 and result["failed"] == 0
    assert set(result["metrics"]) == {"sweep_cells_per_s", "setup_s"}
    assert list(result)[-1] == "compared"
    assert result["compared"]["rel.storm_finish_seconds"]["value"] < 1e-5


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_planted_fault_is_not_correct(cell, fault):
    entry, make = FAULTS[fault]
    with SWEEP.wrapped([entry], make):
        result = measure(small(cell))
    assert not result["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    spec = small(cell)
    query = SWEEP.Query(spec["deployment"], spec["query"], SEED)
    want = query.expected()
    control = query.expected(dtype="float32", pricing_dtype="bfloat16")
    compared = checks.compare(want, [control], 0)
    assert not checks.correct(compared)
    assert compared["gap.origin_egress_bytes"]["value"] > 0
    assert (compared["rel.storm_finish_seconds"]["value"]
            > checks.LIMITS["rel.storm_finish_seconds"])


@pytest.mark.parametrize("cell", CELLS)
def test_serial_replay_agrees_with_the_reference(cell):
    from repro.core import run_sweep
    spec = small(cell, requests=24)
    query = SWEEP.Query(spec["deployment"], spec["query"], SEED)
    report = run_sweep(query.spec, batched=False)
    assert report.serial_cells == 16
    # the serial path prices no storms: its counters are what it answers
    compared = checks.compare(query.expected(), [SWEEP.answers(report)], 0)
    assert all(compared[f"gap.{c}"]["value"] == 0
               for c in reference.COUNTERS)


def test_pricing_reference_agrees_with_the_scalar_solver():
    from repro.kernels.maxmin import maxmin_rates_sparse
    rng = np.random.default_rng(7)
    caps = list(rng.uniform(1e8, 1e10, 12))
    links = [sorted(rng.choice(12, size=rng.integers(1, 5), replace=False))
             for _ in range(60)]
    fcaps = list(rng.uniform(1e7, 3e9, 60))
    want = np.asarray(maxmin_rates_sparse(caps, links, fcaps), np.float64)
    got = pricing.waterfill(caps, links, fcaps)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # every link is within its capacity, and each flow is held by its cap
    # or by a full link
    used = np.zeros(12)
    for f, ls in enumerate(links):
        used[ls] += got[f]
    assert (used <= np.asarray(caps) * (1 + 1e-12)).all()
    full = used >= np.asarray(caps) * (1 - 1e-12)
    for f, ls in enumerate(links):
        assert got[f] >= fcaps[f] * (1 - 1e-12) or full[ls].any()
