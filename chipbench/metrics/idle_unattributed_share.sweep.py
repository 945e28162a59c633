"""Share of the traced window's device-idle time that no program stage
span names: idle time outside every ``repro.`` span but the root
``repro.sweep``, over all idle time, in percent (``bench/spans.py``)."""
from bench import spans


def read(run):
    r = spans.of_run(run)
    if r is None:
        return None
    idle = r["window_s"] - r["busy_s"]
    return None if idle <= 0 else 100.0 * r["idle_unattributed_s"] / idle
