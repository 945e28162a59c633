"""Host seconds of the sweep path per query: each query's wall time less
the spans around the four kernel-layer entries (the stack-distance,
FIFO and cache-state batches and the batched waterfill), averaged over
the window's queries.  What is left is ``run_sweep``'s own Python:
routing, per-cache streams, classification and finalization."""


def read(run):
    if not run.spans or not run.queries:
        return None
    inside = 0.0
    for q in run.queries:
        inside += sum(min(b, q["end"]) - max(a, q["start"])
                      for _, a, b in run.spans
                      if b > q["start"] and a < q["end"])
    wall = sum(q["end"] - q["start"] for q in run.queries)
    return (wall - inside) / len(run.queries)
