"""Eviction-aware cache modelling kernels: Mattson stack distances and a
vectorized single-capacity LRU/FIFO state machine.

The sweep executor (:func:`repro.core.api.run_sweep`) resolves every
batched cell's hit/miss pattern without ever *running* a cache.  For
evicting caches that takes one of two kernels, both jitted and bucketed
to power-of-two shapes like :mod:`repro.kernels.batched_maxmin`:

* :func:`stack_distances_batch` — the Mattson / reuse-distance kernel.
  LRU with byte-granular ``evict_until`` satisfies the *inclusion
  property*: at any instant the resident set is the maximal prefix of
  the recency stack whose cumulative bytes fit the capacity (eviction
  removes from the stack bottom until the insert fits, so the prefix
  stays maximal).  A reference to key ``k`` therefore hits at capacity
  ``C`` iff ``D + size(k) <= C`` where ``D`` is the *byte-weighted stack
  distance*: the total size of distinct keys touched since the previous
  reference to ``k``.  One pass over a request stream prices **every**
  capacity in a sweep column — the distances are capacity-independent;
  each cell only compares them against its own ``C``.

* :func:`cache_sim_batch` — an exact single-capacity LRU/FIFO replay
  for the cells the stack model cannot express: size-aware admission
  (a refused chunk is served but never inserted, yet a still-resident
  copy admitted *earlier* keeps hitting — the filter applies on miss,
  not on lookup), FIFO victim order (not a stack algorithm), and
  payloads larger than the whole cache.  Each reference carries a
  precomputed ``admit`` bit; eviction picks resident keys in ascending
  priority (last-access counter for LRU, admit counter for FIFO) until
  the insert fits, via an in-step sort + exclusive cumulative sum.

Cold restarts appear in both kernels as stream markers: a reset wipes
residency without counting evictions (the disk came back empty; nothing
was *chosen* as a victim), mirroring ``CacheServer.clear``.

Byte counters must be exact — a one-byte error flips an eviction
decision and breaks the sweep's cell-exact parity guarantee — so both
kernels run in float64 under a scoped ``with jax.enable_x64():``
(integers up to 2**53 are exact, far above any capacity the federation
models).  ``tests/test_stack_distance.py`` holds both
kernels byte-equal to a scalar :class:`~repro.core.cache.CacheServer`
oracle replay.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from .. import spans
from .maxmin import _next_pow2

# Bucket floors: streams shorter than these pad up so a sweep's ragged
# stream/key counts land in very few shapes — each (N, K) shape is one
# jit compile, and compile time dominates runtime for these scans.
_FLOOR_N = 256
_FLOOR_K = 64

# One stack-distance problem: (prev, sizes) — per-reference index of the
# previous reference to the same key within the same cold-restart
# segment (-1: none → compulsory miss), and per-reference chunk bytes.
DistanceProblem = Tuple[Sequence[int], Sequence[float]]

# One state-machine problem:
#   (keys, admit, reset, key_sizes, capacity, fifo)
# keys: (N,) int key ids; admit: (N,) bool (miss-path insert allowed —
# admission policy AND capacity refusal, precomputed); reset: (N,) bool
# (cold restart applied before this reference); key_sizes: (K,) bytes
# per key id; capacity: bytes; fifo: True → insertion-order victims.
SimProblem = Tuple[Sequence[int], Sequence[bool], Sequence[bool],
                   Sequence[float], float, bool]


def _distances(prev: jax.Array, sizes: jax.Array) -> jax.Array:
    """Byte-weighted stack distances for one reference stream.

    Scan-carried *marker* array: position ``j`` holds ``sizes[j]`` while
    ``j`` is the most recent reference to its key, else 0.  The distance
    of reference ``i`` is the sum of markers strictly between its
    previous occurrence and ``i`` — markers at or after ``i`` are still
    zero, markers of dead occurrences were zeroed when superseded.
    Compulsory misses (``prev < 0``, including every first reference
    after a cold restart) return ``inf``.
    """
    n = prev.shape[0]
    idx = jnp.arange(n)

    def step(markers, x):
        p, s, i = x
        d = jnp.where(idx > p, markers, 0.0).sum()
        markers = markers.at[jnp.where(p >= 0, p, i)].set(0.0)
        markers = markers.at[i].set(s)
        return markers, jnp.where(p >= 0, d, jnp.inf)

    _, out = jax.lax.scan(step, jnp.zeros(n, sizes.dtype),
                          (prev, sizes, idx))
    return out


def _simulate(keys: jax.Array, admit: jax.Array, reset: jax.Array,
              key_sizes: jax.Array, capacity: jax.Array,
              fifo: jax.Array):
    """Exact LRU/FIFO replay of one stream at one capacity.

    Mirrors :meth:`CacheServer.admit`/``evict_until`` byte for byte:
    a hit touches (LRU) or leaves (FIFO) the key's priority; an
    admitted miss evicts resident keys in ascending priority while the
    bytes freed so far are short of ``usage + size - capacity``, then
    inserts.  Returns ``(hits, evictions, bytes_evicted)``.

    Victim order is kept in *priority slots*: slot ``t`` is written
    only at step ``t``, so slot order IS policy order — an LRU touch
    vacates the key's old slot and occupies slot ``t``, a FIFO hit
    keeps its admit slot.  Eviction is then a prefix of the occupied
    slots (exclusive cumulative bytes short of the need), one O(N)
    cumsum per step instead of a sort or an O(K²) rank comparison —
    both of which are catastrophic inside a vmapped scan.
    """
    K = key_sizes.shape[0]
    n = keys.shape[0]

    def step(carry, x):
        slot_bytes, slot_key, resident, key_slot, usage, ev, evb = carry
        k, a, r, t = x
        slot_bytes = jnp.where(r, 0.0, slot_bytes)
        resident = jnp.where(r, False, resident)
        usage = jnp.where(r, 0.0, usage)
        s = key_sizes[k]
        hit = resident[k]
        do_insert = jnp.logical_and(~hit, a)
        need = jnp.where(do_insert, usage + s - capacity, 0.0)
        excl = jnp.cumsum(slot_bytes) - slot_bytes
        evict_slot = (slot_bytes > 0) & (excl < need)
        freed = jnp.where(evict_slot, slot_bytes, 0.0).sum()
        # scatter-max: stale slot_key duplicates carry zero bytes, so
        # their evict_slot is False and the max is order-independent
        gone = jnp.zeros(K, bool).at[slot_key].max(evict_slot)
        resident = resident & ~gone
        slot_bytes = jnp.where(evict_slot, 0.0, slot_bytes)
        usage = usage - freed
        # occupy slot t on admit or LRU touch; vacate the old slot on
        # touch (an evicted key's old slot is already zero)
        touch = do_insert | (hit & ~fifo)
        old = key_slot[k]
        slot_bytes = slot_bytes.at[old].set(
            jnp.where(hit & touch, 0.0, slot_bytes[old]))
        slot_bytes = slot_bytes.at[t].set(jnp.where(touch, s, 0.0))
        slot_key = slot_key.at[t].set(k)
        key_slot = key_slot.at[k].set(jnp.where(touch, t, old))
        resident = resident.at[k].set(hit | do_insert)
        usage = usage + jnp.where(do_insert, s, 0.0)
        return (slot_bytes, slot_key, resident, key_slot, usage,
                ev + evict_slot.sum().astype(jnp.int32), evb + freed), hit

    carry0 = (jnp.zeros(n, key_sizes.dtype), jnp.zeros(n, jnp.int32),
              jnp.zeros(K, bool), jnp.zeros(K, jnp.int32),
              jnp.asarray(0.0, key_sizes.dtype),
              jnp.asarray(0, jnp.int32), jnp.asarray(0.0, key_sizes.dtype))
    (_, _, _, _, _, ev, evb), hits = jax.lax.scan(
        step, carry0, (keys, admit, reset, jnp.arange(n, dtype=jnp.int32)))
    return hits, ev, evb


def _fifo_replay(keys: jax.Array, sizes: jax.Array, admit: jax.Array,
                 reset: jax.Array, kcum0: jax.Array,
                 capacity: jax.Array):
    """Exact FIFO replay in O(N log N): eviction only ever consumes a
    *prefix* of the admit sequence (hits never touch, re-admits get new
    slots), so the whole cache reduces to a moving byte frontier ``E``
    over the cumulative-admitted-bytes curve.  A key is resident iff
    the cumulative total at its latest admit exceeds ``E``; evicting
    for an insert is one ``searchsorted`` — no per-step cumsum, no
    sort.  Returns ``(hits, evictions, bytes_evicted)``.

    ``kcum0`` is a zeros(K) scratch fixing the per-key state width.
    """
    n = keys.shape[0]
    big = jnp.inf

    def step(carry, x):
        cumB, cumN, kcum, total, totN, E, EN, ev, evb = carry
        k, s, a, r, t = x
        # cold restart: everything already admitted is gone, uncounted
        E = jnp.where(r, total, E)
        EN = jnp.where(r, totN, EN)
        hit = kcum[k] > E
        ins = jnp.logical_and(~hit, a)
        # evict the minimal admit-prefix putting resident + s under cap
        # (ins implies s <= capacity: the host folds the oversize
        # refusal into the admit bit)
        target = total + s - capacity
        do_evict = ins & (target > E)
        j = jnp.searchsorted(cumB, target)
        newE = jnp.where(do_evict, cumB[j], E)
        newN = jnp.where(do_evict, cumN[j], EN)
        ev = ev + (newN - EN)
        evb = evb + (newE - E)
        E, EN = newE, newN
        total = total + jnp.where(ins, s, 0.0)
        totN = totN + ins.astype(jnp.int32)
        cumB = cumB.at[t].set(total)     # flat where not inserted
        cumN = cumN.at[t].set(totN)
        kcum = kcum.at[k].set(jnp.where(ins, total, kcum[k]))
        return (cumB, cumN, kcum, total, totN, E, EN, ev, evb), hit

    zero = jnp.asarray(0.0, sizes.dtype)
    carry0 = (jnp.full(n, big, sizes.dtype), jnp.zeros(n, jnp.int32),
              kcum0, zero, jnp.asarray(0, jnp.int32), zero,
              jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32), zero)
    (_, _, _, _, _, _, _, ev, evb), hits = jax.lax.scan(
        step, carry0, (keys, sizes, admit, reset,
                       jnp.arange(n, dtype=jnp.int32)))
    return hits, ev, evb


_dist_batch = jax.jit(jax.vmap(_distances))
_sim_batch = jax.jit(jax.vmap(_simulate))
_fifo_batch = jax.jit(jax.vmap(_fifo_replay))

# host spans per call and per bucket, under the kinds ``report.solver``
# tallies them by
_STACK_SPANS = spans.kernel("stack")
_FIFO_SPANS = spans.kernel("fifo")
_SIM_SPANS = spans.kernel("cache_sim")


def _note(stats: Optional[Dict], bucket: Tuple[int, ...], pad: int) -> None:
    if stats is not None:
        stats["solve_calls"] += 1
        stats["buckets"].append(bucket)
        stats["padded_problems"] += pad


def _init_stats(stats: Optional[Dict], n: int) -> None:
    if stats is not None:
        stats.update(solve_calls=0, buckets=[], problems=n,
                     padded_problems=0)


def stack_distances_batch(problems: Sequence[DistanceProblem],
                          stats: Optional[Dict] = None) -> List[np.ndarray]:
    """Byte-weighted stack distances for many streams in few jitted calls.

    Streams are padded to power-of-two lengths and same-bucket streams
    stacked (batch padded to a power of two with empty streams), one
    ``jax.jit(jax.vmap(...))`` call per bucket — the JIT cache sees
    O(log) shapes for a whole sweep.  Returns one ``(N_i,)`` float64
    array per problem, ``inf`` marking compulsory misses.
    """
    _init_stats(stats, len(problems))
    call, pack, device, unpack = _STACK_SPANS
    with TraceAnnotation(call, problems=len(problems)):
        out: List[Optional[np.ndarray]] = [None] * len(problems)
        by_bucket: Dict[int, List[int]] = {}
        for i, (prev, _) in enumerate(problems):
            by_bucket.setdefault(_next_pow2(max(len(prev), 1),
                                            floor=_FLOOR_N), []).append(i)
        with jax.enable_x64():
            for Np, idxs in sorted(by_bucket.items()):
                B = _next_pow2(len(idxs), floor=1)
                bucket = f"{B}x{Np}"
                with TraceAnnotation(pack, bucket=bucket):
                    prevs = np.full((B, Np), -1, np.int64)
                    sizes = np.zeros((B, Np), np.float64)
                    for bi, i in enumerate(idxs):
                        p, s = problems[i]
                        prevs[bi, :len(p)] = p
                        sizes[bi, :len(s)] = s
                with TraceAnnotation(device, bucket=bucket):
                    dists = np.asarray(_dist_batch(prevs, sizes))
                _note(stats, (B, Np), B - len(idxs))
                with TraceAnnotation(unpack, bucket=bucket):
                    for bi, i in enumerate(idxs):
                        out[i] = dists[bi, :len(problems[i][0])]
        return [r if r is not None else np.zeros(0) for r in out]


def lru_hits(distances: np.ndarray, ref_sizes: np.ndarray,
             capacity: float) -> np.ndarray:
    """Hit mask at one capacity from precomputed stack distances — the
    per-cell half of the one-pass-per-column contract."""
    return distances + ref_sizes <= capacity


# One FIFO problem: (keys, ref_sizes, admit, reset, n_keys, capacity).
FifoProblem = Tuple[Sequence[int], Sequence[float], Sequence[bool],
                    Sequence[bool], int, float]


def fifo_sim_batch(problems: Sequence[FifoProblem],
                   stats: Optional[Dict] = None
                   ) -> List[Tuple[np.ndarray, int, int]]:
    """Replay many FIFO (stream, capacity) problems in few jitted calls.

    Bucketed like :func:`cache_sim_batch`; capacity is vmapped data, so
    a whole capacity × admission column over one stream shares a device
    call.  Admission is a per-reference bit (refusals — policy or
    oversize — simply never insert), so time-varying filters cost
    nothing here, unlike the LRU stack model.
    """
    _init_stats(stats, len(problems))
    call, pack, device, unpack = _FIFO_SPANS
    with TraceAnnotation(call, problems=len(problems)):
        out: List[Optional[Tuple[np.ndarray, int, int]]] = \
            [None] * len(problems)
        by_bucket: Dict[Tuple[int, int], List[int]] = {}
        for i, (keys, _, _, _, n_keys, _) in enumerate(problems):
            bucket = (_next_pow2(max(len(keys), 1), floor=_FLOOR_N),
                      _next_pow2(max(n_keys, 1), floor=_FLOOR_K))
            by_bucket.setdefault(bucket, []).append(i)
        with jax.enable_x64():
            for (Np, Kp), idxs in sorted(by_bucket.items()):
                B = _next_pow2(len(idxs), floor=1)
                bucket = f"{B}x{Np}x{Kp}"
                with TraceAnnotation(pack, bucket=bucket):
                    keys = np.zeros((B, Np), np.int32)
                    sizes = np.zeros((B, Np), np.float64)
                    admit = np.zeros((B, Np), bool)
                    reset = np.zeros((B, Np), bool)
                    kcum0 = np.zeros((B, Kp), np.float64)
                    cap = np.full(B, np.inf, np.float64)
                    for bi, i in enumerate(idxs):
                        k, s, a, r, _, c = problems[i]
                        keys[bi, :len(k)] = k
                        sizes[bi, :len(s)] = s
                        admit[bi, :len(a)] = a
                        reset[bi, :len(r)] = r
                        cap[bi] = c
                with TraceAnnotation(device, bucket=bucket):
                    hits, ev, evb = (np.asarray(x) for x in
                                     _fifo_batch(keys, sizes, admit, reset,
                                                 kcum0, cap))
                _note(stats, (B, Np, Kp), B - len(idxs))
                with TraceAnnotation(unpack, bucket=bucket):
                    for bi, i in enumerate(idxs):
                        n = len(problems[i][0])
                        out[i] = (hits[bi, :n], int(ev[bi]),
                                  int(round(evb[bi])))
        return [r if r is not None else (np.zeros(0, bool), 0, 0)
                for r in out]


def cache_sim_batch(problems: Sequence[SimProblem],
                    stats: Optional[Dict] = None
                    ) -> List[Tuple[np.ndarray, int, int]]:
    """Replay many (stream, capacity, policy) problems in few jitted
    calls.

    Problems are bucketed by padded ``(N, K)`` shape; capacity and the
    FIFO flag are vmapped *data*, so a whole capacity × policy ×
    admission sweep column over one stream shares a single bucket (and
    a single device call).  Returns ``(hits, evictions, bytes_evicted)``
    per problem, byte-exact against a scalar ``CacheServer`` replay.
    """
    _init_stats(stats, len(problems))
    call, pack, device, unpack = _SIM_SPANS
    with TraceAnnotation(call, problems=len(problems)):
        out: List[Optional[Tuple[np.ndarray, int, int]]] = \
            [None] * len(problems)
        by_bucket: Dict[Tuple[int, int], List[int]] = {}
        for i, (keys, _, _, key_sizes, _, _) in enumerate(problems):
            bucket = (_next_pow2(max(len(keys), 1), floor=_FLOOR_N),
                      _next_pow2(max(len(key_sizes), 1), floor=_FLOOR_K))
            by_bucket.setdefault(bucket, []).append(i)
        with jax.enable_x64():
            for (Np, Kp), idxs in sorted(by_bucket.items()):
                B = _next_pow2(len(idxs), floor=1)
                bucket = f"{B}x{Np}x{Kp}"
                with TraceAnnotation(pack, bucket=bucket):
                    keys = np.zeros((B, Np), np.int32)
                    admit = np.zeros((B, Np), bool)
                    reset = np.zeros((B, Np), bool)
                    ksz = np.zeros((B, Kp), np.float64)
                    cap = np.zeros(B, np.float64)
                    fifo = np.zeros(B, bool)
                    for bi, i in enumerate(idxs):
                        k, a, r, s, c, f = problems[i]
                        keys[bi, :len(k)] = k
                        admit[bi, :len(a)] = a
                        reset[bi, :len(r)] = r
                        ksz[bi, :len(s)] = s
                        cap[bi] = c
                        fifo[bi] = f
                with TraceAnnotation(device, bucket=bucket):
                    hits, ev, evb = (np.asarray(x) for x in
                                     _sim_batch(keys, admit, reset, ksz,
                                                cap, fifo))
                _note(stats, (B, Np, Kp), B - len(idxs))
                with TraceAnnotation(unpack, bucket=bucket):
                    for bi, i in enumerate(idxs):
                        n = len(problems[i][0])
                        out[i] = (hits[bi, :n], int(ev[bi]),
                                  int(round(evb[bi])))
        return [r if r is not None else (np.zeros(0, bool), 0, 0)
                for r in out]
