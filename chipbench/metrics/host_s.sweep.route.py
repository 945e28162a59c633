"""Host seconds per traced query in the program's routing stage: its
``repro.sweep.route`` spans whole (shared federations built, and each
routing column's request arrays, liveness epochs, per-cache streams and
flow tables, the ``.streams`` and ``.flows`` spans inside them)."""
from bench import spans


def read(run):
    return spans.per_query(
        run, lambda r: r["span_s"].get("repro.sweep.route", 0.0))
