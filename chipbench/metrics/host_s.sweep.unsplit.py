"""Host seconds per traced query that the program's stage spans leave
whole: the self time of ``repro.sweep`` (the cell loop and the glue
between stages, outside every stage and kernel span) plus
``repro.sweep.classify`` and ``repro.sweep.l2``."""
from bench import spans


def seconds(r):
    return (r["span_self_s"].get("repro.sweep", 0.0)
            + r["span_s"].get("repro.sweep.classify", 0.0)
            + r["span_s"].get("repro.sweep.l2", 0.0))


def read(run):
    return spans.per_query(run, seconds)
