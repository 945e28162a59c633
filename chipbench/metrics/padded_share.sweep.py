"""Share of the kernel batches' problem slots that are filler: the
all-dummy problems that pad each bucket's batch to a power of two, over
all slots, summed over the stack-distance, FIFO, cache-state and
waterfill batches of one query (``report.solver``)."""

KINDS = (("stack_problems", "stack_padded"), ("fifo_problems", "fifo_padded"),
         ("cache_sim_problems", "cache_sim_padded"),
         ("problems", "padded_problems"))


def read(run):
    if not run.queries:
        return None
    solver = run.queries[-1]["solver"]
    real = sum(solver.get(p, 0) for p, _ in KINDS)
    pad = sum(solver.get(q, 0) for _, q in KINDS)
    if real + pad == 0:
        return None
    return 100.0 * pad / (real + pad)
