"""Host seconds per traced query spent feeding the kernels and reading
their answers: the per-bucket ``.pack`` and ``.unpack`` spans of the
four kernel calls, the self time of each ``repro.kernel.<kind>`` call
(bucketing, outside the per-bucket spans), of ``repro.sweep.distances``
(the ``prev`` chains and end residency around the stack-distance call)
and of ``repro.sweep.price`` (the flow problems and pricing dicts)."""
from bench import spans

KERNELS = ("repro.kernel.stack", "repro.kernel.fifo",
           "repro.kernel.cache_sim", "repro.kernel.waterfill")
SELF = KERNELS + ("repro.sweep.distances", "repro.sweep.price")


def seconds(r):
    whole, own = r["span_s"], r["span_self_s"]
    return (sum(whole.get(k + ".pack", 0.0) + whole.get(k + ".unpack", 0.0)
                for k in KERNELS)
            + sum(own.get(k, 0.0) for k in SELF))


def read(run):
    return spans.per_query(run, seconds)
