"""Device seconds per query of the ``_simulate`` program (its XLA
module's events in the traced window, over the queries traced)."""
from bench.trace import module_seconds

MODULES = ("jit__simulate",)


def read(run):
    if run.trace is None or not run.trace["queries"]:
        return None
    secs = module_seconds(run.trace, MODULES)
    return None if secs is None else secs / run.trace["queries"]
