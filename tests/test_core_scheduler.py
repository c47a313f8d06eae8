"""Unit + property tests for the LifeRaft core: buckets, workload queues,
metrics (Eq. 1/2), cache, schedulers, hybrid planner, adaptive alpha."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    BucketCache,
    CostModel,
    HybridCostModel,
    HybridPlanner,
    LifeRaftScheduler,
    OrderedScheduler,
    Partitioner,
    Query,
    RoundRobinScheduler,
    TradeoffPoint,
    TradeoffTable,
    AlphaController,
    WorkloadManager,
    aged_workload_throughput,
    workload_throughput,
    run_policy,
)
from repro.core.simulate import simulate_batched, simulate_noshare


# ---------------------------------------------------------------- partitioner
class TestPartitioner:
    def test_equal_counts(self):
        keys = np.random.default_rng(0).integers(0, 2**32, 10_000).astype(np.uint64)
        p = Partitioner(keys, objects_per_bucket=1000)
        counts = [s.count for s in p.specs]
        assert sum(counts) == 10_000
        assert all(c == 1000 for c in counts[:-1])

    def test_bucket_of_keys_consistent(self):
        keys = np.random.default_rng(1).integers(0, 2**20, 5_000).astype(np.uint64)
        p = Partitioner(keys, objects_per_bucket=500)
        b = p.bucket_of_keys(keys)
        for bid in range(p.n_buckets):
            spec = p.specs[bid]
            sel = keys[b == bid]
            assert (sel >= spec.key_lo).all()

    def test_range_overlap(self):
        keys = np.arange(1000, dtype=np.uint64) * 10
        p = Partitioner(keys, objects_per_bucket=100)
        bs = p.buckets_for_range(0, int(keys[-1]))
        np.testing.assert_array_equal(bs, np.arange(p.n_buckets))

    def test_object_slice_partition(self):
        keys = np.random.default_rng(2).integers(0, 2**16, 1_000).astype(np.uint64)
        p = Partitioner(keys, objects_per_bucket=100)
        all_idx = np.concatenate([p.object_slice(b) for b in range(p.n_buckets)])
        assert sorted(all_idx.tolist()) == list(range(1000))


# ---------------------------------------------------------------- metrics
class TestMetrics:
    def test_eq1_formula(self):
        cm = CostModel(T_b=1.2, T_m=0.13e-3)
        w = 500
        assert workload_throughput(w, False, cm) == pytest.approx(
            w / (1.2 + 0.13e-3 * w)
        )
        assert workload_throughput(w, True, cm) == pytest.approx(w / (0.13e-3 * w))

    def test_cached_bucket_preferred(self):
        cm = CostModel()
        assert workload_throughput(100, True, cm) > workload_throughput(100, False, cm)

    def test_zero_queue(self):
        assert workload_throughput(0, False, CostModel()) == 0.0

    @given(st.floats(0.0, 1.0), st.integers(1, 10_000), st.integers(1, 10_000))
    @settings(max_examples=100, deadline=None)
    def test_eq2_blend_bounds(self, alpha, w1, w2):
        """U_a interpolates: alpha=0 ranks by U_t only, alpha=1 by age only."""
        cm = CostModel()
        sizes = {1: w1, 2: w2}
        ages = {1: 50.0, 2: 500.0}
        cached = {1: False, 2: False}
        ua = aged_workload_throughput(sizes, ages, cached, cm, alpha)
        if alpha == 0.0:
            ut1 = workload_throughput(w1, False, cm)
            ut2 = workload_throughput(w2, False, cm)
            assert (ua[1] >= ua[2]) == (ut1 >= ut2)
        if alpha == 1.0:
            assert ua[2] > ua[1]  # strictly older wins

    def test_alpha_validation(self):
        with pytest.raises(ValueError):
            aged_workload_throughput({}, {}, {}, CostModel(), 1.5)

    def test_monotone_in_queue_size_cold(self):
        cm = CostModel()
        us = [workload_throughput(w, False, cm) for w in (1, 10, 100, 1000)]
        assert us == sorted(us)


# ---------------------------------------------------------------- cache
class TestBucketCache:
    def test_lru_eviction_order(self):
        c = BucketCache(2)
        c.access(1)
        c.access(2)
        c.access(1)  # 2 is now LRU
        ev = c.access(3)
        assert ev == [2]
        assert c.contains(1) and c.contains(3)

    def test_hit_rate(self):
        c = BucketCache(4)
        for b in [1, 2, 1, 1, 3]:
            c.access(b)
        assert c.stats.hits == 2 and c.stats.misses == 3
        assert c.stats.hit_rate == pytest.approx(0.4)

    def test_pinned_not_evicted(self):
        c = BucketCache(1)
        c.access(1)
        c.pin(1)
        c.access(2)
        assert c.contains(1)
        c.unpin(1)
        c.access(3)
        assert not c.contains(1) or not c.contains(2)

    @given(st.lists(st.integers(0, 20), min_size=1, max_size=300), st.integers(1, 10))
    @settings(max_examples=60, deadline=None)
    def test_capacity_invariant(self, accesses, cap):
        c = BucketCache(cap)
        for b in accesses:
            c.access(b)
        assert len(c) <= cap
        assert c.stats.accesses == len(accesses)


# ---------------------------------------------------------------- workload
def _mk_query(qid, t, buckets_per_obj, n_obj=3):
    # keys equal bucket ids when bucket_of_range is identity-range below
    lo = np.array([b for b in buckets_per_obj[:n_obj]], dtype=np.uint64)
    return Query(qid, t, lo, lo)


def _identity_range(lo, hi):
    return np.arange(lo, hi + 1)


class TestWorkloadManager:
    def test_decomposition_and_completion(self):
        wm = WorkloadManager(_identity_range)
        q = _mk_query(0, 0.0, [1, 1, 2])
        units = wm.submit(q)
        assert {u.bucket_id for u in units} == {1, 2}
        assert wm.queue(1).size == 2 and wm.queue(2).size == 1
        assert wm.complete_bucket(1, 1.0) == []  # still waiting on 2
        assert wm.complete_bucket(2, 2.0) == [0]
        assert wm.response_times()[0] == pytest.approx(2.0)

    def test_interleaving(self):
        wm = WorkloadManager(_identity_range)
        wm.submit(_mk_query(0, 0.0, [5, 5, 5]))
        wm.submit(_mk_query(1, 1.0, [5, 6, 6]))
        assert wm.queue(5).size == 4  # both queries share bucket 5's queue
        assert len(wm.queue(5)) == 2  # as two work units

    def test_ages(self):
        wm = WorkloadManager(_identity_range)
        wm.submit(_mk_query(0, 0.0, [1, 1, 1]))
        wm.submit(_mk_query(1, 5.0, [1, 1, 1]))
        ages = wm.ages_ms(10.0)
        assert ages[1] == pytest.approx(10_000.0)  # oldest request dominates


    @pytest.mark.parametrize(
        "case, seed",
        [("random", s) for s in range(4)]
        + [("span2", 10), ("span3plus", 11), ("boundaries", 12),
           ("empty", 13), ("full_range", 14), ("full_range", 15)],
    )
    def test_keys_decompose_keeps_the_range_loop_order(self, case, seed):
        """The vectorised key lookup gives the per-object range loop's
        mapping element for element: buckets in first-insertion order,
        each bucket's indices ascending, spanning objects included."""
        rng = np.random.default_rng(seed)
        keys = rng.integers(0, 2**32, int(rng.integers(500, 5_000)))
        p = Partitioner(keys.astype(np.uint64),
                        objects_per_bucket=int(rng.integers(50, 400)))
        bound = np.array([s.key_lo for s in p.specs], np.int64)
        top = p.specs[-1].key_hi
        nb = p.n_buckets
        n = int(rng.integers(50, 600))
        lo = rng.integers(0, top, n)
        hi = lo + rng.integers(0, 2**16, n)
        pick = rng.random(n) < 0.3
        b = rng.integers(0, nb - 1, n)
        if case == "span2":  # the last key of b through the first of b+1
            lo[pick], hi[pick] = bound[b[pick] + 1] - 1, bound[b[pick] + 1]
        elif case == "span3plus":
            far = np.minimum(b + rng.integers(2, 6, n), nb - 1)
            lo[pick], hi[pick] = bound[b[pick]] + 1, bound[far[pick]] + 1
        elif case == "boundaries":  # on the first and last buckets' edges
            edge = np.array([0, bound[0], bound[0] + 1, bound[-1] - 1,
                             bound[-1], top - 1, top, 2 * top])
            lo, hi = rng.choice(edge, n), rng.choice(edge, n)
            lo, hi = np.minimum(lo, hi), np.maximum(lo, hi)
        elif case == "empty":
            lo = hi = np.zeros(0, np.int64)
        elif case == "full_range":
            lo[n // 2], hi[n // 2] = 0, np.iinfo(np.int64).max
        q = Query(0, 0.0, lo.astype(np.uint64), hi.astype(np.uint64))
        by_keys = WorkloadManager(p.buckets_for_range, p.bucket_of_keys)
        by_range = WorkloadManager(p.buckets_for_range)
        got, want = by_keys._decompose(q), by_range._decompose(q)
        assert list(got.items()) == list(want.items())
        assert by_keys.objects_by_keys == by_range.objects_by_range == q.n_objects
        assert by_keys.objects_by_range == by_range.objects_by_keys == 0
        spanning = int(np.sum(p.bucket_of_keys(q.keys_hi)
                              > p.bucket_of_keys(q.keys_lo)))
        assert by_keys.spanning_objects == by_range.spanning_objects == spanning
        if case in ("span2", "span3plus"):
            widths = {len(p.buckets_for_range(int(a), int(z)))
                      for a, z in zip(q.keys_lo, q.keys_hi)}
            assert (2 in widths) if case == "span2" else max(widths) >= 3
        if case == "full_range":
            assert len(got) == nb and all(n // 2 in v for v in got.values())


# ---------------------------------------------------------------- schedulers
class TestSchedulers:
    def _setup(self):
        wm = WorkloadManager(_identity_range)
        wm.submit(_mk_query(0, 0.0, [1, 1, 1]))  # bucket 1: 3 objects, old
        wm.submit(_mk_query(1, 9.0, [2] * 3, n_obj=3))
        wm.queues[2].units[0].object_idx = np.arange(500)  # bucket 2: huge, new
        wm.queues[2]._size = 500
        return wm, BucketCache(4)

    def test_greedy_picks_contention(self):
        wm, cache = self._setup()
        s = LifeRaftScheduler(CostModel(), alpha=0.0)
        assert s.select(wm, cache, 10.0).bucket_id == 2

    def test_aged_picks_oldest(self):
        wm, cache = self._setup()
        s = LifeRaftScheduler(CostModel(), alpha=1.0)
        assert s.select(wm, cache, 10.0).bucket_id == 1

    def test_ordered_equals_alpha1(self):
        wm, cache = self._setup()
        a = OrderedScheduler(CostModel()).select(wm, cache, 10.0)
        b = LifeRaftScheduler(CostModel(), alpha=1.0).select(wm, cache, 10.0)
        assert a.bucket_id == b.bucket_id

    def test_cache_residency_bias(self):
        """Equal queues: the cached bucket must win under alpha=0."""
        wm = WorkloadManager(_identity_range)
        wm.submit(_mk_query(0, 0.0, [1, 1, 1]))
        wm.submit(_mk_query(1, 0.0, [2, 2, 2]))
        cache = BucketCache(4)
        cache.access(2)
        s = LifeRaftScheduler(CostModel(), alpha=0.0)
        assert s.select(wm, cache, 1.0).bucket_id == 2

    def test_rr_cycles_in_id_order(self):
        wm = WorkloadManager(_identity_range)
        for qid, b in enumerate([3, 1, 7]):
            wm.submit(_mk_query(qid, float(qid), [b] * 3))
        rr = RoundRobinScheduler(CostModel())
        cache = BucketCache(4)
        order = []
        for _ in range(3):
            d = rr.select(wm, cache, 0.0)
            order.append(d.bucket_id)
            wm.complete_bucket(d.bucket_id, 0.0)
        assert order == [1, 3, 7]

    def test_empty_returns_none(self):
        wm = WorkloadManager(_identity_range)
        assert LifeRaftScheduler(CostModel()).select(wm, BucketCache(2), 0.0) is None


# ---------------------------------------------------------------- hybrid
class TestHybrid:
    def test_break_even_matches_paper(self):
        """Paper Fig. 2: break-even ~3% of a 10k-object bucket."""
        h = HybridCostModel(T_b=1.2, T_m=0.13e-3, T_probe=4.13e-3)
        assert h.break_even_queue() == pytest.approx(300, rel=0.01)

    def test_planner_small_queue_indexed(self):
        h = HybridCostModel()
        p = HybridPlanner(h, objects_per_bucket=10_000)
        assert p.plan(10, in_cache=False).strategy == "indexed"
        assert p.plan(5_000, in_cache=False).strategy == "scan"

    def test_cached_bucket_always_scans(self):
        p = HybridPlanner(HybridCostModel(), objects_per_bucket=10_000)
        assert p.plan(2, in_cache=True).strategy == "scan"

    def test_fixed_threshold(self):
        p = HybridPlanner(
            HybridCostModel(), objects_per_bucket=10_000, threshold_frac=0.03
        )
        assert p.plan(299, False).strategy == "indexed"
        assert p.plan(301, False).strategy == "scan"


# ---------------------------------------------------------------- adaptive
class TestAdaptive:
    def _table(self):
        t = TradeoffTable()
        t.add(0.1, [TradeoffPoint(0.0, 1.0, 10.0), TradeoffPoint(1.0, 0.93, 4.6)])
        t.add(0.5, [TradeoffPoint(0.0, 1.0, 8.0), TradeoffPoint(0.25, 0.8, 6.4)])
        return t

    def test_select_alpha_low_saturation(self):
        # 7% throughput loss for 54% response gain is within 20% tolerance.
        assert self._table().select_alpha(0.1, tolerance=0.2) == 1.0

    def test_select_alpha_high_saturation(self):
        assert self._table().select_alpha(0.5, tolerance=0.1) == 0.0

    def test_controller_moves_incrementally(self):
        ctl = AlphaController(self._table(), tolerance=0.2, initial_alpha=0.0,
                              max_step=0.1, halflife_s=1.0)
        # Slow arrivals -> low saturation -> alpha drifts up, capped per step.
        a_prev = 0.0
        for t in np.arange(0, 100, 10.0):
            a = ctl.update_on_arrival(float(t))
            assert a - a_prev <= 0.1 + 1e-9
            a_prev = a
        assert a_prev > 0.5


# ---------------------------------------------------------------- simulator
class TestSimulator:
    def _trace(self, n=50, seed=0, hot=4, buckets=30, gap=0.2):
        rng = np.random.default_rng(seed)
        qs = []
        t = 0.0
        for qid in range(n):
            t += rng.exponential(gap)
            if rng.random() < 0.7:
                b = rng.integers(0, hot)
            else:
                b = rng.integers(hot, buckets)
            ks = np.full(rng.integers(2, 20), b, dtype=np.uint64)
            qs.append(Query(qid, t, ks, ks))
        return qs

    def test_all_queries_complete(self):
        qs = self._trace()
        for pol, a in [("noshare", 0), ("rr", 0), ("liferaft", 0.0), ("liferaft", 0.7)]:
            r = run_policy(pol, qs, _identity_range, CostModel(), alpha=a)
            assert r.n_queries == len(qs), pol

    def test_sharing_beats_noshare(self):
        # Paper-like cache pressure: many more buckets than cache slots.
        qs = self._trace(n=300, seed=1, hot=12, buckets=400, gap=0.05)
        greedy = run_policy(
            "liferaft", qs, _identity_range, CostModel(), alpha=0.0, cache_capacity=8
        )
        noshare = run_policy(
            "noshare", qs, _identity_range, CostModel(), cache_capacity=8
        )
        assert greedy.query_throughput > 1.3 * noshare.query_throughput
        assert greedy.mean_response < noshare.mean_response

    def test_greedy_highest_throughput(self):
        # Saturated + cache-pressured, as in the paper's Fig. 7 regime.
        qs = self._trace(n=300, seed=2, hot=12, buckets=400, gap=0.05)
        rs = {
            a: run_policy(
                "liferaft", qs, _identity_range, CostModel(), alpha=a,
                cache_capacity=8,
            )
            for a in (0.0, 1.0)
        }
        assert rs[0.0].query_throughput >= rs[1.0].query_throughput

    def test_cache_hit_rate_higher_for_greedy(self):
        """Paper §6: 40% (alpha=0) vs 7% (alpha=1) serviced from cache."""
        qs = self._trace(n=300, seed=3, hot=3, buckets=60)
        g = run_policy("liferaft", qs, _identity_range, CostModel(), alpha=0.0,
                       cache_capacity=5)
        o = run_policy("liferaft", qs, _identity_range, CostModel(), alpha=1.0,
                       cache_capacity=5)
        assert g.cache_hit_rate > o.cache_hit_rate

    def test_makespan_conservation(self):
        """Busy time can never exceed makespan; work conserves."""
        qs = self._trace(n=100, seed=4)
        r = run_policy("liferaft", qs, _identity_range, CostModel(), alpha=0.3)
        assert r.busy_time <= r.makespan + 1e-6

    @given(st.floats(0.0, 1.0))
    @settings(max_examples=20, deadline=None)
    def test_any_alpha_completes(self, alpha):
        qs = self._trace(n=40, seed=5)
        r = run_policy("liferaft", qs, _identity_range, CostModel(), alpha=alpha)
        assert r.n_queries == 40
