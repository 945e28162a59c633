"""Stack-distance / cache state-machine kernels vs a scalar
``CacheServer`` oracle replay.

The sweep executor's cell-exact parity rests on these kernels answering
hit/miss/eviction questions byte-identically to the real cache state
machine, so the oracle here is the :class:`~repro.core.cache.CacheServer`
itself (``lookup``/``admit``/``clear``), not a reimplementation.
"""
import random

import numpy as np
import pytest

from repro.core import (CacheServer, Coord, Payload, SizeAwareAdmission,
                        Topology)
from repro.kernels.stack_distance import (_block_width, cache_sim_batch,
                                          fifo_sim_batch, lru_hits,
                                          stack_distances_batch)


def _cache(capacity, policy="lru", admission=None):
    topo = Topology()
    topo.add_site("s")
    node = topo.add_node(f"c-{policy}-{capacity}", Coord("s"), 1e10)
    return CacheServer(node.name, node, int(capacity), policy=policy,
                       admission=admission)


def _trace(seed, n=300, n_keys=14, max_size=20, reset_rate=0.02):
    """A random keyed reference stream with sizes and cold restarts."""
    rng = random.Random(seed)
    sizes = [rng.randint(1, max_size) for _ in range(n_keys)]
    keys = [rng.randrange(n_keys) for _ in range(n)]
    resets = [i > 0 and rng.random() < reset_rate for i in range(n)]
    return keys, sizes, resets


def _oracle(keys, sizes, resets, capacity, policy="lru", fraction=None,
            admit=None):
    """Replay the stream through a real CacheServer.  ``admit``, where
    given, is a per-reference admission decision (a filter that flips
    mid-stream): a miss whose bit is False is not offered to ``admit``."""
    admission = SizeAwareAdmission(fraction) if fraction is not None else None
    c = _cache(capacity, policy=policy, admission=admission)
    hits = []
    for i, (k, r) in enumerate(zip(keys, resets)):
        if r:
            c.clear()
        path = f"/k{k}"
        if c.lookup(path, 0) is not None:
            hits.append(True)
            continue
        hits.append(False)
        if admit is not None and not admit[i]:
            continue
        c.admit(path, 0, Payload.synthetic(sizes[k], path, 0),
                object_size=sizes[k])
    return (np.asarray(hits), c.stats.evictions, c.stats.bytes_evicted,
            c.stats.admission_rejects, c.stats.oversize_rejects)


def _prev_indices(keys, resets):
    prev, last = [], {}
    for i, (k, r) in enumerate(zip(keys, resets)):
        if r:
            last = {}
        prev.append(last.get(k, -1))
        last[k] = i
    return prev


class TestStackDistances:
    def test_lru_hits_match_cache_server_at_every_capacity(self):
        """One distance pass answers every capacity in a sweep column —
        the Mattson inclusion property with byte-granular evict_until."""
        keys, sizes, resets = _trace(seed=1)
        ref_sizes = np.asarray([sizes[k] for k in keys], float)
        dist = stack_distances_batch([(_prev_indices(keys, resets),
                                       ref_sizes)])[0]
        for capacity in (20, 25, 33, 47, 64, 100, 10_000):
            hits = lru_hits(dist, ref_sizes, capacity)
            oracle_hits, *_ = _oracle(keys, sizes, resets, capacity)
            assert (hits == oracle_hits).all(), capacity

    def test_compulsory_misses_are_inf(self):
        dist = stack_distances_batch([([-1, -1, 0, -1], [3.0] * 4)])[0]
        assert np.isinf(dist[[0, 1, 3]]).all()
        assert dist[2] == 3.0  # one distinct key (ref 1) in between

    def test_distance_counts_distinct_key_bytes(self):
        # stream A B C B A: A's reuse distance = |B| + |C| (B once)
        keys = [0, 1, 2, 1, 0]
        sizes = {0: 5.0, 1: 7.0, 2: 11.0}
        prev = _prev_indices(keys, [False] * 5)
        dist = stack_distances_batch(
            [(prev, [sizes[k] for k in keys])])[0]
        assert dist[4] == 7.0 + 11.0
        assert dist[3] == 11.0

    def test_bucketing_telemetry(self):
        """Same-bucket streams share one jitted call; ragged lengths
        land in O(log) buckets (floored so short streams coalesce),
        batch padded to a power of two."""
        problems = [(_prev_indices(*t), [1.0] * len(t[0]))
                    for t in (([0] * 5, [False] * 5),
                              ([1] * 7, [False] * 7),
                              ([2] * 300, [False] * 300))]
        stats = {}
        stack_distances_batch(problems, stats=stats)
        assert stats["problems"] == 3
        assert stats["solve_calls"] == 2          # {256-floor ×2, 512 ×1}
        assert sorted(stats["buckets"]) == [(1, 512), (2, 256)]
        assert stats["padded_problems"] == 0      # both batches pow2 already


class TestCacheStateMachine:
    @pytest.mark.parametrize("policy", ["lru", "fifo"])
    @pytest.mark.parametrize("capacity", [25, 40, 77, 1000])
    def test_hits_and_evictions_match_cache_server(self, policy, capacity):
        keys, sizes, resets = _trace(seed=2)
        admit = np.asarray([sizes[k] <= capacity for k in keys])
        (hits, ev, evb), = cache_sim_batch(
            [(keys, admit, resets, np.asarray(sizes, float),
              float(capacity), policy == "fifo")])
        o_hits, o_ev, o_evb, *_ = _oracle(keys, sizes, resets, capacity,
                                          policy=policy)
        assert (hits == o_hits).all()
        assert (ev, evb) == (o_ev, o_evb)

    def test_admission_filter_respects_resident_copies(self):
        """The size-aware filter applies on *miss*, not on lookup: a
        copy admitted while the filter allowed it keeps hitting."""
        keys, sizes, resets = _trace(seed=3, max_size=40)
        capacity, fraction = 120, 0.2
        admit = np.asarray([sizes[k] <= fraction * capacity for k in keys])
        (hits, ev, evb), = cache_sim_batch(
            [(keys, admit, resets, np.asarray(sizes, float),
              float(capacity), False)])
        o_hits, o_ev, o_evb, o_rej, _ = _oracle(
            keys, sizes, resets, capacity, fraction=fraction)
        assert (hits == o_hits).all()
        assert (ev, evb) == (o_ev, o_evb)
        # policy rejects derive from the hit mask outside the kernel
        assert int((~hits & ~admit).sum()) == o_rej

    def test_oversize_chunks_never_insert(self):
        """Chunks larger than the cache: always a miss, never perturb
        the stack — mirrors the CacheServer.admit oversize refusal."""
        keys, sizes, resets = _trace(seed=4, max_size=60)
        capacity = 50
        admit = np.asarray([sizes[k] <= capacity for k in keys])
        (hits, ev, evb), = cache_sim_batch(
            [(keys, admit, resets, np.asarray(sizes, float),
              float(capacity), False)])
        o_hits, o_ev, o_evb, _, o_over = _oracle(keys, sizes, resets,
                                                 capacity)
        assert (hits == o_hits).all()
        assert (ev, evb) == (o_ev, o_evb)
        assert int((~hits & ~admit).sum()) == o_over

    def test_capacity_policy_column_shares_one_call(self):
        """A capacity × policy sweep column over one stream is vmapped
        data, not separate compiles — one bucket, one device call."""
        keys, sizes, resets = _trace(seed=5)
        ksz = np.asarray(sizes, float)
        problems = []
        for capacity in (30, 50, 90, 200):
            for fifo in (False, True):
                admit = np.asarray([sizes[k] <= capacity for k in keys])
                problems.append((keys, admit, resets, ksz,
                                 float(capacity), fifo))
        stats = {}
        results = cache_sim_batch(problems, stats=stats)
        assert stats["solve_calls"] == 1
        assert stats["problems"] == 8
        for (hits, ev, evb), (capacity, fifo) in zip(
                results, [(c, f) for c in (30, 50, 90, 200)
                          for f in (False, True)]):
            o_hits, o_ev, o_evb, *_ = _oracle(
                keys, sizes, resets, capacity,
                policy="fifo" if fifo else "lru")
            assert (hits == o_hits).all() and (ev, evb) == (o_ev, o_evb)


def _replay_all(streams, policy, kernel, stats=None):
    """Replay ``(keys, sizes, resets, capacity, admit)`` streams in one
    batched call of ``kernel`` (``"sim"``: ``cache_sim_batch``,
    ``"fifo"``: ``fifo_sim_batch``) and hold each against the
    CacheServer oracle; returns the oracle's ``(hits, evictions,
    bytes_evicted)`` per stream."""
    streams = [(keys, sizes, resets, capacity,
                [sizes[k] <= capacity for k in keys] if admit is None
                else admit)
               for keys, sizes, resets, capacity, admit in streams]
    if kernel == "fifo":
        results = fifo_sim_batch(
            [(keys, np.asarray([sizes[k] for k in keys], float),
              np.asarray(admit), resets, len(sizes), float(capacity))
             for keys, sizes, resets, capacity, admit in streams],
            stats=stats)
    else:
        results = cache_sim_batch(
            [(keys, np.asarray(admit), resets, np.asarray(sizes, float),
              float(capacity), policy == "fifo")
             for keys, sizes, resets, capacity, admit in streams],
            stats=stats)
    out = []
    for (hits, ev, evb), (keys, sizes, resets, capacity, admit) in zip(
            results, streams):
        o_hits, o_ev, o_evb, *_ = _oracle(keys, sizes, resets, capacity,
                                          policy=policy, admit=admit)
        assert (hits == o_hits).all()
        assert (ev, evb) == (o_ev, o_evb)
        out.append((o_hits, o_ev, o_evb))
    return out


def _block_edge_stream(n, seed):
    """A stream in the ``n`` bucket whose first evictions land on the
    block edges of its ``W = _block_width(n)``: ``2W`` one-byte keys fill
    a ``2W``-byte cache, a ``W``-byte insert takes block 0 exactly and a
    ``2W``-byte one everything left; then a random tail of every key
    with cold restarts and a zero-byte key.  Returns ``(keys, sizes,
    resets, capacity)``."""
    rng = random.Random(seed)
    W = _block_width(n)
    sizes = [1] * (2 * W) + [W, 2 * W, 0] + [rng.randint(1, W)
                                             for _ in range(20)]
    keys = list(range(2 * W + 2))
    while len(keys) <= n // 2:
        keys.append(rng.randrange(len(sizes)))
    resets = [i > 2 * W + 2 and rng.random() < 0.01
              for i in range(len(keys))]
    return keys, sizes, resets, 2 * W


def _replay_both(keys, sizes, resets, capacity, policy, admit=None,
                 stats=None, kernel="sim"):
    """One stream through ``_replay_all``."""
    return _replay_all([(keys, sizes, resets, capacity, admit)], policy,
                       kernel, stats=stats)[0]


# the LRU and FIFO state machine, and the FIFO-only frontier kernel
@pytest.mark.parametrize(
    "policy,kernel", [("lru", "sim"), ("fifo", "sim"), ("fifo", "fifo")],
    ids=["lru", "fifo", "fifo_frontier"])
class TestSlotFrontier:
    """The state machine evicts along a moving slot frontier searched on
    two-level block sums, and the FIFO kernel along an admit frontier
    searched on two-level block ends (``W`` slots a block: 16 in the 256
    bucket, 32 in the 2048 bucket, 64 in the 4096 bucket).  These
    streams put eviction runs, cold restarts and zero-byte keys where
    that structure has its edges."""

    def test_eviction_runs_span_many_blocks_in_the_4096_bucket(self, policy,
                                                               kernel):
        rng = random.Random(14)
        small, capacity = 600, 2000
        sizes = [rng.randint(1, 3) for _ in range(small)]
        sizes += [capacity] + [rng.randint(700, 1500) for _ in range(5)]
        keys = list(range(small))                               # fill
        keys += [rng.randrange(small) for _ in range(1000)]     # holes
        keys += [small]                          # needs the whole cache
        keys += [rng.randrange(small) if rng.random() < 0.97
                 else small + 1 + rng.randrange(5) for _ in range(1400)]
        stats = {}
        _, ev, _ = _replay_both(keys, sizes, [False] * len(keys),
                                capacity, policy, stats=stats,
                                kernel=kernel)
        assert stats["buckets"] == [(1, 4096, 1024)]
        assert ev > small

    def test_evictions_land_on_block_edges(self, policy, kernel):
        # 48 one-byte keys fill slots 0..47 of a 48-byte cache; a 16-byte
        # insert then takes block 0 exactly, a 32-byte one blocks 1 and 2
        unit = 48
        sizes = [1] * unit + [16, 32, 48]
        keys = list(range(unit)) + [unit, unit + 1, unit + 2]
        keys += list(range(unit)) + [unit + 1, unit]
        resets = [False] * len(keys)
        _, ev, evb = _replay_both(keys, sizes, resets, unit, policy,
                                  kernel=kernel)
        assert _replay_both(keys[:unit + 2], sizes, resets, unit,
                            policy, kernel=kernel)[1:] == (48, 48)
        assert ev > 48 and evb > 48

    def test_cold_restart_right_after_an_eviction(self, policy, kernel):
        rng = random.Random(141)
        sizes = [rng.randint(1, 9) for _ in range(30)] + [40]
        keys, resets = [], []
        for _ in range(6):
            fill = [rng.randrange(30) for _ in range(25)]
            keys += fill + [30] + fill[:5]
            resets += [False] * 26 + [True] + [False] * 4
        hits, ev, _ = _replay_both(keys, sizes, resets, 60, policy,
                                   kernel=kernel)
        assert ev > 0 and not any(hits[i] for i, r in enumerate(resets)
                                  if r)

    def test_admission_flip_while_a_copy_is_resident(self, policy, kernel):
        rng = random.Random(142)
        sizes = [rng.randint(1, 12) for _ in range(40)]
        keys = [rng.randrange(40) for _ in range(240)]
        # the filter admits, refuses for a stretch, then admits again:
        # copies admitted before the flip keep hitting through it
        admit = [not 80 <= i < 160 for i in range(len(keys))]
        hits, ev, _ = _replay_both(keys, sizes, [False] * len(keys), 90,
                                   policy, admit=admit, kernel=kernel)
        assert hits[80:160].any() and ev > 0

    def test_insert_that_needs_every_resident_byte(self, policy, kernel):
        # LRU touches leave holes above the frontier; the 60-byte insert
        # then needs every resident byte of the 60-byte cache
        sizes = [10, 20, 5, 15, 10, 60, 7]
        keys = [0, 1, 2, 3, 4, 1, 3, 0, 5, 6, 0, 5]
        hits, ev, evb = _replay_both(keys, sizes, [False] * len(keys), 60,
                                     policy, kernel=kernel)
        assert ev >= 5 and evb >= 60

    def test_zero_byte_keys_below_and_above_the_last_victim(self, policy,
                                                            kernel):
        # Z0 A B Z1 C fill 15 bytes; D evicts Z0 (0 bytes, below the
        # last victim A, counted) and A, and leaves Z1 (above) resident
        z0, a, b, z1, c, d, e = range(7)
        sizes = [0, 5, 5, 0, 5, 5, 10]
        keys = [z0, a, b, z1, c, d, z1, z0, e]
        hits, ev, evb = _replay_both(keys, sizes, [False] * len(keys), 15,
                                     policy, kernel=kernel)
        assert list(hits) == [False] * 6 + [True, False, False]
        assert ev >= 2

    def test_zero_byte_key_at_the_frontier_stays_resident(self, policy,
                                                          kernel):
        # Z0 is admitted where nothing is resident and Z1 where the last
        # victim A's bytes end: both share a byte total with the
        # frontier, and both are resident
        z0, a, b, z1 = range(4)
        sizes = [0, 5, 5, 0]
        keys = [z0, z0, a, z1, b, z1, z0]
        hits, ev, evb = _replay_both(keys, sizes, [False] * len(keys), 5,
                                     policy, kernel=kernel)
        assert list(hits) == [False, True, False, False, False, True, False]
        assert (ev, evb) == (2, 5)

    @pytest.mark.parametrize("n", [256, 2048])
    def test_block_edges_at_the_floor_and_in_a_non_square_bucket(
            self, policy, kernel, n):
        # 256: 16 blocks of W = 16 slots; 2048: 64 blocks of W = 32
        keys, sizes, resets, capacity = _block_edge_stream(n, seed=17)
        W = _block_width(n)
        stats = {}
        _, ev, _ = _replay_both(keys, sizes, resets, capacity, policy,
                                stats=stats, kernel=kernel)
        assert [bucket[1] for bucket in stats["buckets"]] == [n]
        assert ev > 2 * W
        # the W-byte insert takes block 0 exactly, then keeps hitting
        head = keys[:2 * W + 1] + [2 * W] * (len(keys) - 2 * W - 1)
        assert _replay_both(head, sizes, [False] * len(head), capacity,
                            policy, kernel=kernel)[1:] == (W, W)

    def test_rows_of_one_bucket_end_at_different_steps(self, policy,
                                                       kernel):
        # five streams of 513 to 1024 references, each at its own
        # capacity, share the 1024 bucket (W = 32) and one call: every
        # row but the longest runs on past its end, and three rows pad
        rng = random.Random(172)
        sizes = [rng.randint(0, 40) for _ in range(120)]
        streams = []
        for n, capacity in [(1000, 150), (513, 400), (777, 90),
                            (640, 1000), (1024, 60)]:
            keys = [rng.randrange(len(sizes)) for _ in range(n)]
            resets = [i > 0 and rng.random() < 0.005 for i in range(n)]
            streams.append((keys, sizes, resets, capacity, None))
        stats = {}
        out = _replay_all(streams, policy, kernel, stats=stats)
        assert stats["buckets"] == [(8, 1024, 128)]
        assert all(ev > 0 for _, ev, _ in out)
