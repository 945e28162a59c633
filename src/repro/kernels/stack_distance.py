"""Eviction-aware cache modelling kernels: Mattson stack distances and a
vectorized single-capacity LRU/FIFO state machine.

The sweep executor (:func:`repro.core.api.run_sweep`) resolves every
batched cell's hit/miss pattern without ever *running* a cache.  For
evicting caches that takes one of three kernels, all jitted and run in
power-of-two buckets by :func:`repro.kernels.batching.run_buckets`:

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
  precomputed ``admit`` bit; eviction takes resident keys in ascending
  priority (last access for LRU, admission for FIFO) until the insert
  fits.  Priority is a slot index written once, at the reference's own
  step, so eviction only ever takes a *prefix* of slot order: the
  resident set is every occupied slot at or above a frontier that only
  moves forward, and finding the last victim is a search on two-level
  byte prefixes over the slots, O(sqrt N) lanes a step.

* :func:`fifo_sim_batch` — the FIFO-only replay: with no touches the
  same prefix argument holds over the admit sequence alone, so the
  frontier is found on the cumulative-admitted curve, kept on two
  levels: a count of the block ends below the target, then of the
  entries below it in that block's row, O(sqrt N) lanes a step.  The
  batch axis is explicit, not ``vmap``-ed, so that the step index is
  one scalar and each step writes the curve in place: batched, the
  TPU compiler relayouts the whole curve on every step to search it.

Cold restarts appear in every kernel as stream markers: a reset wipes
residency without counting evictions (the disk came back empty; nothing
was *chosen* as a victim), mirroring ``CacheServer.clear``.

Byte counters must be exact — a one-byte error flips an eviction
decision and breaks the sweep's cell-exact parity guarantee — so the
kernels run in float64 under a scoped ``with jax.enable_x64():``
(integers up to 2**53 are exact, far above any capacity the federation
models).  ``tests/test_stack_distance.py`` holds the
kernels byte-equal to a scalar :class:`~repro.core.cache.CacheServer`
oracle replay.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import spans
from .batching import _next_pow2, run_buckets

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


def _block_width(n: int) -> int:
    """Slots per block of the two-level prefixes of :func:`_simulate`
    and :func:`_fifo_replay`: the power of two at or below ``sqrt(n)``
    (64 at 4096, 16 at 256), so both levels are about ``sqrt(n)`` lanes
    wide."""
    return 1 << ((max(n, 1).bit_length() - 1) // 2)


def _simulate(keys: jax.Array, admit: jax.Array, reset: jax.Array,
              key_sizes: jax.Array, capacity: jax.Array,
              fifo: jax.Array):
    """Exact LRU/FIFO replay of one stream at one capacity.

    Mirrors :meth:`CacheServer.admit`/``evict_until`` byte for byte:
    a hit touches (LRU) or leaves (FIFO) the key's priority; an
    admitted miss evicts resident keys in ascending priority until
    ``usage + size <= capacity``, then inserts.  Returns ``(hits,
    evictions, bytes_evicted)``.

    Victim order is kept in *priority slots*: slot ``t`` is written
    only at step ``t``, so slot order IS policy order — an LRU touch
    vacates the key's old slot and occupies slot ``t``, a FIFO hit
    keeps its admit slot.  Eviction then only ever takes a *prefix* of
    slot order, so the cache is a moving slot frontier ``F``: slots
    below ``F`` are gone, and a key is resident iff its latest slot is
    at or above ``F``.  ``F`` never moves back: an eviction takes every
    occupied slot from ``F`` up to the first one at which the bytes
    taken reach the need (each earlier one still leaves the insert
    short, so ``evict_until`` takes it too), an LRU touch only vacates
    a resident slot (at or above ``F``), and a cold restart moves ``F``
    to ``t``.  Nothing behind ``F`` is written or cleared again, so
    ``base_b``/``base_n`` (bytes and occupied slots below ``F``) stay
    exact as the FIFO kernel's ``E``/``EN`` do, and the usage is the
    byte total less ``base_b``.

    Above ``F`` an LRU touch leaves holes, so the byte and occupancy
    prefixes over slots are kept on two levels that take point updates:
    ``(n/W, W)`` prefixes within each block and ``(n/W,)`` prefixes of
    the block totals (:func:`_block_width`), a point update being a
    suffix add on one row of each.  A victim search counts the blocks
    whose prefix is short of ``total + size - capacity``, then the
    slots of the block after them; the new ``F`` is the slot after the
    last victim, and the bytes and slots evicted are its prefix less
    ``base``.  Each step touches O(sqrt(n)) lanes and takes no cumsum.
    A 0-byte slot counts as occupied, so one below the last victim is
    evicted and counted, as ``evict_until`` does.
    """
    n = keys.shape[0]
    W = _block_width(n)
    nb = -(-n // W)
    dt = key_sizes.dtype
    blocks = jnp.arange(nb, dtype=jnp.int32)
    lanes = jnp.arange(W, dtype=jnp.int32)

    def add(pre_b, pre_n, cum_b, cum_n, slot, db, dn):
        # slot gains db bytes and dn occupants: every prefix from it on
        ob, ow = jnp.divmod(slot, W)
        row = lanes >= ow
        pre_b = pre_b.at[ob].add(jnp.where(row, db, 0.0))
        pre_n = pre_n.at[ob].add(jnp.where(row, dn, 0))
        cum_b = cum_b + jnp.where(blocks >= ob, db, 0.0)
        cum_n = cum_n + jnp.where(blocks >= ob, dn, 0)
        return pre_b, pre_n, cum_b, cum_n

    def step(carry, x):
        (pre_b, pre_n, cum_b, cum_n, key_slot, F, base_b, base_n,
         ev, evb) = carry
        k, a, r, t = x
        total_b, total_n = cum_b[nb - 1], cum_n[nb - 1]
        # cold restart: every written slot is gone, uncounted
        F = jnp.where(r, t, F)
        base_b = jnp.where(r, total_b, base_b)
        base_n = jnp.where(r, total_n, base_n)
        s = key_sizes[k]
        old = key_slot[k]
        hit = old >= F
        do_insert = jnp.logical_and(~hit, a)
        # the slot prefix has to reach `target` bytes; the host folds
        # oversize refusals into `a`
        target = total_b + s - capacity
        do_evict = do_insert & (target > base_b)
        b = jnp.sum(cum_b < target, dtype=jnp.int32)
        found = b < nb
        b = jnp.minimum(b, nb - 1)
        row_b = pre_b[b] + (cum_b[b] - pre_b[b, W - 1])
        row_n = pre_n[b] + (cum_n[b] - pre_n[b, W - 1])
        j = jnp.minimum(jnp.sum(row_b < target, dtype=jnp.int32), W - 1)
        # no slot reaches the target only for an insert larger than
        # the cache: evict everything
        new_F = jnp.where(found, b * W + j + 1, t)
        new_b = jnp.where(found, row_b[j], total_b)
        new_n = jnp.where(found, row_n[j], total_n)
        ev = ev + jnp.where(do_evict, new_n - base_n, 0)
        evb = evb + jnp.where(do_evict, new_b - base_b, 0.0)
        F = jnp.where(do_evict, new_F, F)
        base_b = jnp.where(do_evict, new_b, base_b)
        base_n = jnp.where(do_evict, new_n, base_n)
        # an LRU hit vacates its old slot; an admit or LRU hit
        # occupies slot t
        vac = hit & ~fifo
        touch = do_insert | vac
        state = add(pre_b, pre_n, cum_b, cum_n, jnp.maximum(old, 0),
                    jnp.where(vac, -s, 0.0), -vac.astype(jnp.int32))
        state = add(*state, t, jnp.where(touch, s, 0.0),
                    touch.astype(jnp.int32))
        key_slot = key_slot.at[k].set(jnp.where(touch, t, old))
        return (*state, key_slot, F, base_b, base_n, ev, evb), hit

    zero_b = jnp.asarray(0.0, dt)
    zero_n = jnp.asarray(0, jnp.int32)
    carry0 = (jnp.zeros((nb, W), dt), jnp.zeros((nb, W), jnp.int32),
              jnp.zeros(nb, dt), jnp.zeros(nb, jnp.int32),
              jnp.full(key_sizes.shape[0], -1, jnp.int32), zero_n,
              zero_b, zero_n, zero_n, zero_b)
    (*_, ev, evb), hits = jax.lax.scan(
        step, carry0, (keys, admit, reset, jnp.arange(n, dtype=jnp.int32)))
    return hits, ev, evb


def _fifo_replay(keys: jax.Array, sizes: jax.Array, admit: jax.Array,
                 reset: jax.Array, kcum0: jax.Array,
                 capacity: jax.Array):
    """Exact FIFO replay of a batch of streams, each at its own
    capacity: eviction only ever consumes a *prefix* of the admit
    sequence (hits never touch, re-admits get new slots), so the whole
    cache reduces to a moving frontier over the admit sequence: ``E``
    bytes and ``EN`` admits lie behind it.  A key is resident iff its
    latest admit's ordinal (1, 2, ... over the stream) exceeds ``EN``;
    an ordinal and not a byte total, because a 0-byte admit shares its
    byte total with the admit before it and may be on the other side of
    the frontier.  Returns ``(hits, evictions, bytes_evicted)``, shaped
    ``(B, N)``, ``(B,)``, ``(B,)``.

    The curve (written at every step, flat where nothing is admitted,
    ``inf`` ahead of the step) is carried on two levels, ``(B, N/W, W)``
    rows of ``W`` slots (:func:`_block_width`) and the ``(B, N/W)``
    block ends: the running total at the latest write into each block,
    ``inf`` where nothing is written.  The frontier search for an
    evicting insert counts the block ends below its target, then the
    entries below it in that one row: on a non-decreasing curve that is
    ``searchsorted(side="left")`` in O(sqrt N) lanes, with no nested
    search loop.

    The batch axis is explicit rather than ``vmap``-ed so that the step
    index is one scalar for the whole batch: every per-step write into
    the curve is a ``dynamic_update_slice`` of a ``(B, 1, 1)`` slice.
    Under ``vmap`` the index is batched, the write becomes a scatter,
    and the TPU compiler keeps the carried curve in a layout with the
    batch axis minor, which it then copies in full on every step to
    search it.

    ``kcum0`` is a (B, K) array of which only the shape is read: it
    fixes the width of the per-key ordinals.
    """
    B, n = keys.shape
    W = _block_width(n)
    nb = -(-n // W)
    dt = sizes.dtype
    # per-problem picks and one per-problem write: gathers and a scatter
    # along the batch axis, with no index arrays built every step
    take = jax.vmap(lambda table, i: table[i])
    put = jax.vmap(lambda table, i, v: table.at[i].set(v))
    zero = jnp.asarray(0, jnp.int32)

    def step(carry, x):
        cum_b, cum_n, ends, kord, total, tot_n, E, EN, ev, evb, t = carry
        k, s, a, r = x
        # cold restart: everything already admitted is gone, uncounted
        E = jnp.where(r, total, E)
        EN = jnp.where(r, tot_n, EN)
        ko = take(kord, k)
        hit = ko > EN
        ins = ~hit & a
        # evict the minimal admit-prefix putting resident + s under cap
        # (ins implies s <= capacity: the host folds the oversize
        # refusal into the admit bit, so a target beyond every written
        # slot only arises with no eviction)
        target = total + s - capacity
        do_evict = ins & (target > E)
        b = jnp.minimum(jnp.sum(ends < target[:, None], axis=1,
                                dtype=jnp.int32), nb - 1)
        row_b = take(cum_b, b)
        j = jnp.minimum(jnp.sum(row_b < target[:, None], axis=1,
                                dtype=jnp.int32), W - 1)
        newE = jnp.where(do_evict, take(row_b, j), E)
        newN = jnp.where(do_evict, take(take(cum_n, b), j), EN)
        ev = ev + (newN - EN)
        evb = evb + (newE - E)
        E, EN = newE, newN
        total = total + jnp.where(ins, s, 0.0)
        tot_n = tot_n + ins.astype(jnp.int32)
        tb, tw = jnp.divmod(t, W)
        cum_b = jax.lax.dynamic_update_slice(cum_b, total[:, None, None],
                                             (zero, tb, tw))
        cum_n = jax.lax.dynamic_update_slice(cum_n, tot_n[:, None, None],
                                             (zero, tb, tw))
        ends = jax.lax.dynamic_update_slice(ends, total[:, None], (zero, tb))
        kord = put(kord, k, jnp.where(ins, tot_n, ko))
        return (cum_b, cum_n, ends, kord, total, tot_n, E, EN, ev, evb,
                t + 1), hit

    zero_b = jnp.zeros(B, dt)
    zero_n = jnp.zeros(B, jnp.int32)
    carry0 = (jnp.full((B, nb, W), jnp.inf, dt),
              jnp.zeros((B, nb, W), jnp.int32),
              jnp.full((B, nb), jnp.inf, dt),
              jnp.zeros(kcum0.shape, jnp.int32),
              zero_b, zero_n, zero_b, zero_n, zero_n, zero_b, zero)
    (*_, ev, evb, _), hits = jax.lax.scan(
        step, carry0, (keys.T, sizes.T, admit.T, reset.T))
    return hits.T, ev, evb


_dist_batch = jax.jit(jax.vmap(_distances))
_sim_batch = jax.jit(jax.vmap(_simulate))
_fifo_batch = jax.jit(_fifo_replay)

# host spans per call and per bucket, under the kinds ``report.solver``
# tallies them by
_STACK_SPANS = spans.kernel("stack")
_FIFO_SPANS = spans.kernel("fifo")
_SIM_SPANS = spans.kernel("cache_sim")


def _padded(n: int, floor: int) -> int:
    return _next_pow2(max(n, 1), floor=floor)


def _pack_distances(members: Sequence[DistanceProblem], B: int,
                    key: Tuple[int, ...]) -> Tuple[np.ndarray, ...]:
    (Np,) = key
    prevs = np.full((B, Np), -1, np.int64)
    sizes = np.zeros((B, Np), np.float64)
    for bi, (p, s) in enumerate(members):
        prevs[bi, :len(p)] = p
        sizes[bi, :len(s)] = s
    return prevs, sizes


def stack_distances_batch(problems: Sequence[DistanceProblem],
                          stats: Optional[Dict] = None) -> List[np.ndarray]:
    """Byte-weighted stack distances for many streams in few jitted calls.

    Streams are padded to power-of-two lengths and same-bucket streams
    stacked (batch padded to a power of two with empty streams), one
    ``jax.jit(jax.vmap(...))`` call per bucket — the JIT cache sees
    O(log) shapes for a whole sweep.  Returns one ``(N_i,)`` float64
    array per problem, ``inf`` marking compulsory misses.
    """
    with jax.enable_x64():
        return run_buckets(
            _STACK_SPANS, problems,
            lambda pr: (_padded(len(pr[0]), _FLOOR_N),),
            _pack_distances, _dist_batch,
            lambda dists, row, pr: dists[row, :len(pr[0])], stats)


def lru_hits(distances: np.ndarray, ref_sizes: np.ndarray,
             capacity: float) -> np.ndarray:
    """Hit mask at one capacity from precomputed stack distances — the
    per-cell half of the one-pass-per-column contract."""
    return distances + ref_sizes <= capacity


# One FIFO problem: (keys, ref_sizes, admit, reset, n_keys, capacity).
FifoProblem = Tuple[Sequence[int], Sequence[float], Sequence[bool],
                    Sequence[bool], int, float]


def _unpack_replay(outputs: Tuple[np.ndarray, ...], row: int,
                   problem: Tuple) -> Tuple[np.ndarray, int, int]:
    hits, ev, evb = outputs
    return (hits[row, :len(problem[0])], int(ev[row]),
            int(round(evb[row])))


def _pack_fifo(members: Sequence[FifoProblem], B: int,
               key: Tuple[int, ...]) -> Tuple[np.ndarray, ...]:
    Np, Kp = key
    keys = np.zeros((B, Np), np.int32)
    sizes = np.zeros((B, Np), np.float64)
    admit = np.zeros((B, Np), bool)
    reset = np.zeros((B, Np), bool)
    kcum0 = np.zeros((B, Kp), np.float64)
    cap = np.full(B, np.inf, np.float64)
    for bi, (k, s, a, r, _, c) in enumerate(members):
        keys[bi, :len(k)] = k
        sizes[bi, :len(s)] = s
        admit[bi, :len(a)] = a
        reset[bi, :len(r)] = r
        cap[bi] = c
    return keys, sizes, admit, reset, kcum0, cap


def fifo_sim_batch(problems: Sequence[FifoProblem],
                   stats: Optional[Dict] = None
                   ) -> List[Tuple[np.ndarray, int, int]]:
    """Replay many FIFO (stream, capacity) problems in few jitted calls.

    Bucketed like :func:`cache_sim_batch`; capacity is a row of the
    batch, so a whole capacity × admission column over one stream
    shares a device call.  Admission is a per-reference bit (refusals —
    policy or oversize — simply never insert), so time-varying filters
    cost nothing here, unlike the LRU stack model.
    """
    with jax.enable_x64():
        return run_buckets(
            _FIFO_SPANS, problems,
            lambda pr: (_padded(len(pr[0]), _FLOOR_N),
                        _padded(pr[4], _FLOOR_K)),
            _pack_fifo, _fifo_batch, _unpack_replay, stats)


def _pack_sim(members: Sequence[SimProblem], B: int,
              key: Tuple[int, ...]) -> Tuple[np.ndarray, ...]:
    Np, Kp = key
    keys = np.zeros((B, Np), np.int32)
    admit = np.zeros((B, Np), bool)
    reset = np.zeros((B, Np), bool)
    ksz = np.zeros((B, Kp), np.float64)
    cap = np.zeros(B, np.float64)
    fifo = np.zeros(B, bool)
    for bi, (k, a, r, s, c, f) in enumerate(members):
        keys[bi, :len(k)] = k
        admit[bi, :len(a)] = a
        reset[bi, :len(r)] = r
        ksz[bi, :len(s)] = s
        cap[bi] = c
        fifo[bi] = f
    return keys, admit, reset, ksz, cap, fifo


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
    with jax.enable_x64():
        return run_buckets(
            _SIM_SPANS, problems,
            lambda pr: (_padded(len(pr[0]), _FLOOR_N),
                        _padded(len(pr[3]), _FLOOR_K)),
            _pack_sim, _sim_batch, _unpack_replay, stats)
