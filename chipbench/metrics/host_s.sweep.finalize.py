"""Host seconds per traced query in the program's per-cell finalize
stage (``repro.sweep.finalize``: each cell's counters, fetch results,
flow specs, summary and fits)."""
from bench import spans


def read(run):
    return spans.per_query(
        run, lambda r: r["span_s"].get("repro.sweep.finalize", 0.0))
