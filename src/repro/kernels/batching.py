"""The bucket loop every batched kernel wrapper runs.

A sweep hands each kernel many problems of ragged shape.  Compiling one
program per shape would dominate the run, so every ``*_batch`` wrapper
(:func:`~repro.kernels.stack_distance.stack_distances_batch`,
:func:`~repro.kernels.stack_distance.fifo_sim_batch`,
:func:`~repro.kernels.stack_distance.cache_sim_batch`,
:func:`~repro.kernels.batched_maxmin.maxmin_rates_batch`) groups its
problems by padded shape and makes one jitted call per group, with the
batch itself padded to a power of two by dummy problems.  The JIT cache
then sees O(log) shapes for a whole sweep.  :func:`run_buckets` is that
loop, so the batch-padding policy is decided here and nowhere else; a
wrapper supplies only its bucket key and how to pack and unpack it.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple, TypeVar

import jax
import numpy as np
from jax.profiler import TraceAnnotation

P = TypeVar("P")
R = TypeVar("R")


def _next_pow2(n: int, floor: int = 8) -> int:
    p = floor
    while p < n:
        p *= 2
    return p


def run_buckets(names: Tuple[str, str, str, str], problems: Sequence[P],
                bucket_of: Callable[[P], Tuple[int, ...]],
                pack: Callable[[Sequence[P], int, Tuple[int, ...]], Tuple],
                program: Callable, unpack: Callable[[object, int, P], R],
                stats: Optional[Dict] = None) -> List[R]:
    """Answer ``problems`` in input order, one ``program`` call a bucket.

    ``bucket_of(problem)`` is the padded shape a problem runs at.
    Buckets run in sorted key order, each at batch ``B``, the next power
    of two at or above its member count.  ``pack(members, B, key)``
    fills the padded arrays, ``program(*arrays)`` is the jitted call,
    whose outputs are copied to the host, and ``unpack(outputs, row,
    problem)`` slices out one problem's answer.

    ``names`` are the kernel's spans (:func:`repro.spans.kernel`): the
    call (metadata ``problems``), then per bucket (metadata ``bucket``,
    ``"x"``-joined ``(B, *key)``) its ``.pack``, ``.device`` (the call
    through its copy back) and ``.unpack``.  ``stats``, when given, gets
    ``solve_calls``, ``buckets`` (``(B, *key)`` per call), ``problems``
    and ``padded_problems`` (the dummy batch rows).
    """
    if stats is not None:
        stats.update(solve_calls=0, buckets=[], problems=len(problems),
                     padded_problems=0)
    call, pack_span, device_span, unpack_span = names
    with TraceAnnotation(call, problems=len(problems)):
        by_bucket: Dict[Tuple[int, ...], List[int]] = {}
        for i, problem in enumerate(problems):
            by_bucket.setdefault(bucket_of(problem), []).append(i)
        out: List = [None] * len(problems)
        for key, idxs in sorted(by_bucket.items()):
            B = _next_pow2(len(idxs), floor=1)
            bucket = "x".join(str(d) for d in (B, *key))
            with TraceAnnotation(pack_span, bucket=bucket):
                arrays = pack([problems[i] for i in idxs], B, key)
            with TraceAnnotation(device_span, bucket=bucket):
                outputs = jax.tree.map(np.asarray, program(*arrays))
            if stats is not None:
                stats["solve_calls"] += 1
                stats["buckets"].append((B, *key))
                stats["padded_problems"] += B - len(idxs)
            with TraceAnnotation(unpack_span, bucket=bucket):
                for row, i in enumerate(idxs):
                    out[i] = unpack(outputs, row, problems[i])
        return out
