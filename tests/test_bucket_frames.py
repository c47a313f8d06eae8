"""Bucket frames: a cache miss copies into a recycled frame.

``BucketStore.read(b, out=frame)`` gathers the same bytes as the
allocating ``read(b)``; ``CrossMatchEngine`` keeps a free list of frames
and hands a frame back only at the end of the round in which its bucket
left the cache, so no round ever reads a frame that another bucket's copy
has overwritten.  Results are those of the allocating read on every
executor path.
"""
import sys
import threading

import numpy as np
import pytest

from repro.core import HybridCostModel, HybridPlanner, PrefetchConfig
from repro.core.bucket import SPLIT_BYTES, BucketStore, Partitioner
from repro.crossmatch import CrossMatchEngine, TraceConfig, make_catalog, make_trace


def _wide_store(n_objects, per_bucket, row_bytes, seed=3):
    """A store with an opaque ``row`` column beside the narrow ones, as
    the benchmark's catalog has, in a random (ingest) order."""
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 1 << 40, size=n_objects).astype(np.uint64)
    payload = {
        "positions": rng.normal(size=(n_objects, 3)),
        "mags": rng.uniform(14.0, 24.0, size=n_objects),
        "htm": keys,
        "row": rng.integers(0, 256, size=(n_objects, row_bytes - 40), dtype=np.uint8),
    }
    return BucketStore(Partitioner(keys, objects_per_bucket=per_bucket), payload)


def _frame_for(store, b):
    frame = store.empty_frame()
    n = store.spec(b).count
    return {k: a[:n] for k, a in frame.items()}


@pytest.mark.parametrize(
    "n_objects, per_bucket, row_bytes",
    [
        (1_050, 100, 400),  # 40 KB buckets: one thread, a short last bucket
        (2_300, 1_100, 4_000),  # 4.4 MB buckets: split over the copy pool
    ],
    ids=["small", "split"],
)
def test_read_into_frame_equals_allocating_read(n_objects, per_bucket, row_bytes):
    store = _wide_store(n_objects, per_bucket, row_bytes)
    big = per_bucket * row_bytes >= SPLIT_BYTES
    assert big == (row_bytes == 4_000)
    for b in range(store.n_buckets):
        want = store.read(b)
        out = _frame_for(store, b)
        got = store.read(b, out=out)
        assert got is out
        assert set(got) == set(want)
        for k in want:
            assert got[k].dtype == want[k].dtype and got[k].shape == want[k].shape
            assert got[k].tobytes() == want[k].tobytes(), (b, k)
        # Every column moves, the opaque row included: n x row_bytes.
        n = store.spec(b).count
        assert sum(v.nbytes for v in got.values()) == n * row_bytes


def test_frame_reads_from_many_threads_stay_exact():
    """Split reads from several threads at once (shard drain threads share
    one copy pool) each fill their own frame exactly."""
    store = _wide_store(4_400, 1_100, 4_000)
    want = [store.read(b) for b in range(store.n_buckets)]
    errors = []

    def worker(tid):
        try:
            for i in range(6):
                b = (tid + i) % store.n_buckets
                got = store.read(b, out=_frame_for(store, b))
                if any(got[k].tobytes() != want[b][k].tobytes() for k in got):
                    errors.append((tid, b))
        except Exception as exc:  # noqa: BLE001 - reported below
            errors.append((tid, repr(exc)))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(t,)) for t in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert not errors


def test_store_refuses_a_payload_shorter_than_the_keys():
    keys = np.arange(10, dtype=np.uint64)
    with pytest.raises(ValueError):
        BucketStore(Partitioner(keys, objects_per_bucket=4), {"x": np.zeros(9)})


# ---------------------------------------------------------------- the engine
class _AllocatingEngine(CrossMatchEngine):
    """The engine with every miss read into new arrays (no frames)."""

    def _read_miss(self, bucket_id):
        return {}, self.catalog.store.read(bucket_id)


@pytest.fixture(scope="module")
def catalog():
    # 21 buckets, the last one short (50 objects).
    return make_catalog(n_objects=2_050, objects_per_bucket=100, htm_level=6, seed=17)


def _trace(catalog, n=30, predicates=False, seed=19):
    trace = make_trace(
        catalog,
        TraceConfig(n_queries=n, arrival_rate=2.0, objects_median=40, seed=seed),
    )
    if predicates:
        rng = np.random.default_rng(5)
        for q in trace:
            q.meta["radius"] = float(rng.choice([2e-3, 4e-3, 8e-3]))
            q.meta["mag_cut"] = float(rng.choice([23.0, 24.0]))
    return trace


def _serve(eng, trace):
    """One round after each arrival, then drain: buckets come back after
    they were evicted, and queues stay short enough for indexed plans."""
    for q in sorted(trace, key=lambda q: q.arrival_time):
        eng.sim_clock = max(eng.sim_clock, q.arrival_time)
        eng.submit(q)
        eng.step()
    while eng.step() is not None:
        pass
    eng.close()
    return eng.results


def _assert_same_results(a, b):
    assert set(a) == set(b)
    for qid in a:
        assert len(a[qid]) == len(b[qid]), qid
        for ma, mb in zip(a[qid], b[qid]):
            for f in ("probe_idx", "match_obj", "best_dot", "n_candidates"):
                x, y = getattr(ma, f), getattr(mb, f)
                assert x.dtype == y.dtype and x.tobytes() == y.tobytes(), (qid, f)


PATHS = {
    "single": (dict(fuse_k=1), False),
    "fused": (dict(fuse_k=3), False),
    "per_predicate": (dict(fuse_k=2), True),
    "shared": (dict(fuse_k=2, shared_plan=True, share_width=2), True),
}


@pytest.mark.parametrize("hybrid", [False, True], ids=["scan", "hybrid"])
@pytest.mark.parametrize("path", sorted(PATHS))
def test_results_identical_with_and_without_frames(catalog, path, hybrid):
    kwargs, predicates = PATHS[path]
    trace = _trace(catalog, predicates=predicates)

    def run(cls):
        planner = (
            HybridPlanner(HybridCostModel(), objects_per_bucket=100, threshold_frac=0.3)
            if hybrid
            else None
        )
        eng = cls(
            catalog, match_radius_rad=4e-3, cache_capacity=3, hybrid=planner, **kwargs
        )
        bypass = []
        note = eng.cache.note_bypass_miss
        eng.cache.note_bypass_miss = lambda: (bypass.append(1), note())
        return eng, _serve(eng, trace), len(bypass)

    ref, want, _ = run(_AllocatingEngine)
    eng, got, n_bypass = run(CrossMatchEngine)
    _assert_same_results(want, got)
    assert eng.cache.stats.hits == ref.cache.stats.hits
    assert eng.cache.stats.misses == ref.cache.stats.misses
    assert eng.frames_reused + eng.frames_allocated == eng.cache.stats.misses
    assert eng.frames_reused > 0
    if hybrid:
        assert n_bypass > 0  # the indexed cold-read branch took frames too


@pytest.mark.parametrize("capacity, fuse_k", [(3, 1), (3, 4), (5, 2)])
def test_frame_allocations_stay_within_capacity_plus_fuse_k(catalog, capacity, fuse_k):
    eng = CrossMatchEngine(
        catalog, match_radius_rad=4e-3, cache_capacity=capacity, fuse_k=fuse_k
    )
    _serve(eng, _trace(catalog, n=80, seed=23))
    misses = eng.cache.stats.misses
    assert misses > 4 * (capacity + fuse_k)  # a long run of misses
    assert eng.frames_allocated <= capacity + fuse_k
    assert eng.frames_reused + eng.frames_allocated == misses
    # Held frames: the resident ones; none left released after the round.
    assert not eng._released
    assert len(eng._frame_of) <= capacity


def test_same_round_eviction_keeps_the_evicted_payload(catalog):
    """Capacity 3 with one slot pinned and 4 buckets a round: a later
    insert of a round evicts an earlier bucket of the same round, and the
    round's next miss must not copy into that bucket's frame while the
    round still routes from it."""
    trace = _trace(catalog, n=40, seed=29)
    pinned = 20  # resident and pinned: two slots are left to the rounds

    def run(cls):
        eng = cls(
            catalog, match_radius_rad=2e-2, cache_capacity=3, fuse_k=4, mag_cut=19.0
        )
        eng.cache.access(pinned, catalog.store.read(pinned))
        eng.cache.pin(pinned)
        evicted_in_round, stale = [], []
        route = eng._route

        def checked_route(bucket_id, *args, **kwargs):
            # Routing runs after the round's last fetch: the payload it
            # reads must still hold this bucket's bytes.
            payload = args[6]
            want = catalog.store.read(bucket_id)
            if any(payload[k].tobytes() != want[k].tobytes() for k in want):
                stale.append(bucket_id)
            return route(bucket_id, *args, **kwargs)

        def tap(outcome):
            ids = [d.bucket_id for d in outcome.decisions]
            evicted_in_round.append(sum(not eng.cache.contains(b) for b in ids))

        eng._route = checked_route
        eng.loop.add_round_tap(tap)
        return eng, eng.run(trace), evicted_in_round, stale

    _, want, _, _ = run(_AllocatingEngine)
    eng, got, evicted_in_round, stale = run(CrossMatchEngine)
    assert sum(n >= 2 for n in evicted_in_round) > 0  # evicted, then missed again
    assert eng.frames_reused > 0
    assert not stale
    _assert_same_results(want, got)


def test_prefetch_reads_still_allocate(catalog):
    """The prefetch pipeline stages with the allocating read: only the
    engine's own demand misses pass a frame."""
    store = catalog.store
    real = store.read
    calls = []

    def recording_read(bucket_id, out=None):
        calls.append((threading.current_thread() is threading.main_thread(), out))
        return real(bucket_id, out=out)

    store.read = recording_read  # before the pipeline binds its fetch
    try:
        eng = CrossMatchEngine(
            catalog, match_radius_rad=4e-3, fuse_k=2, cache_capacity=6,
            prefetch=PrefetchConfig(horizon=4, depth=3),
        )
        _serve(eng, _trace(catalog, n=20))
    finally:
        store.read = real
    assert eng.loop.prefetch.fills > 0
    staged = [out for main, out in calls if not main]
    assert staged and all(out is None for out in staged)
    assert all(out is not None for main, out in calls if main)
