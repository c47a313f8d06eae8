"""End-to-end cross-match engine tests: correctness of the full Fig. 3
pipeline (scheduler -> cache -> kernel join -> per-query routing)."""
import numpy as np
import pytest

from repro.core import CostModel, LifeRaftScheduler, RoundRobinScheduler
from repro.crossmatch import CrossMatchEngine, TraceConfig, make_catalog, make_trace
from repro.core.workload import Query
from repro.core.sfc import htm_id


@pytest.fixture(scope="module")
def catalog():
    return make_catalog(n_objects=8_000, objects_per_bucket=200, htm_level=7, seed=5)


def _probe_query(catalog, qid, idx, radius=3e-3, level_offset=2):
    """A query probing exact catalog positions (guaranteed matches)."""
    pos = catalog.positions[idx]
    ids = htm_id(pos, level=catalog.level)
    shift = np.uint64(2 * level_offset)
    anc = ids >> shift
    return Query(
        query_id=qid,
        arrival_time=float(qid),
        keys_lo=anc << shift,
        keys_hi=((anc + np.uint64(1)) << shift) - np.uint64(1),
        payload={"positions": pos},
    )


class TestEngineCorrectness:
    def test_self_probes_all_match(self, catalog):
        eng = CrossMatchEngine(catalog, match_radius_rad=1e-3)
        q = _probe_query(catalog, 0, np.arange(0, 512))
        eng.submit(q)
        while eng.step() is not None:
            pass
        got = np.concatenate([r.probe_idx for r in eng.results[0]])
        assert len(np.unique(got)) == 512  # every probe found its source
        rows = np.concatenate([r.match_obj for r in eng.results[0]])
        assert set(rows.tolist()) <= set(range(catalog.n_objects))

    def test_matches_are_true_neighbors(self, catalog):
        eng = CrossMatchEngine(catalog, match_radius_rad=5e-3)
        rng = np.random.default_rng(0)
        idx = rng.integers(0, catalog.n_objects, 128)
        eng.submit(_probe_query(catalog, 0, idx))
        while eng.step() is not None:
            pass
        for r in eng.results[0]:
            probe = catalog.positions[idx[r.probe_idx]]
            matched = catalog.positions[r.match_obj]
            dots = np.sum(probe * matched, axis=1)
            assert (dots >= np.cos(5e-3) - 1e-5).all()

    def test_pallas_and_jnp_paths_agree(self, catalog):
        rng = np.random.default_rng(1)
        idx = rng.integers(0, catalog.n_objects, 64)
        out = {}
        for use_pallas in (False, True):
            eng = CrossMatchEngine(
                catalog, match_radius_rad=2e-3, use_pallas=use_pallas
            )
            eng.submit(_probe_query(catalog, 0, idx))
            while eng.step() is not None:
                pass
            got = {
                (int(p), int(m))
                for r in eng.results[0]
                for p, m in zip(r.probe_idx, r.match_obj)
            }
            out[use_pallas] = got
        assert out[False] == out[True]

    def test_scheduler_choice_does_not_change_results(self, catalog):
        trace = make_trace(
            catalog, TraceConfig(n_queries=12, arrival_rate=2.0,
                                 objects_median=60, seed=9),
        )
        outs = []
        for sched in (
            LifeRaftScheduler(CostModel(), alpha=0.0),
            LifeRaftScheduler(CostModel(), alpha=1.0),
            RoundRobinScheduler(CostModel()),
        ):
            eng = CrossMatchEngine(catalog, scheduler=sched, match_radius_rad=4e-3)
            res = eng.run(trace)
            outs.append({
                qid: {(int(p), int(m)) for r in groups
                      for p, m in zip(r.probe_idx, r.match_obj)}
                for qid, groups in res.items()
            })
        assert outs[0] == outs[1] == outs[2]  # scheduling is result-invariant

    def test_fused_multibucket_matches_single(self, catalog):
        """fuse_k>1 (one segmented device call for the top-k buckets) must
        produce exactly the matches of the per-bucket path."""
        trace = make_trace(
            catalog, TraceConfig(n_queries=16, arrival_rate=2.0,
                                 objects_median=60, seed=13),
        )
        outs = {}
        for k in (1, 4):
            eng = CrossMatchEngine(catalog, match_radius_rad=4e-3, fuse_k=k)
            res = eng.run(trace)
            outs[k] = {
                qid: {(int(p), int(m)) for r in groups
                      for p, m in zip(r.probe_idx, r.match_obj)}
                for qid, groups in res.items()
            }
            if k > 1:  # dispatch amortization actually happened
                assert eng.dispatches < eng.batches
        assert outs[1] == outs[4]

    def test_fused_pallas_matches_jnp(self, catalog):
        rng = np.random.default_rng(2)
        idx = rng.integers(0, catalog.n_objects, 96)
        out = {}
        for use_pallas in (False, True):
            eng = CrossMatchEngine(
                catalog, match_radius_rad=2e-3, use_pallas=use_pallas, fuse_k=3
            )
            eng.submit(_probe_query(catalog, 0, idx))
            while eng.step() is not None:
                pass
            out[use_pallas] = {
                (int(p), int(m))
                for r in eng.results[0]
                for p, m in zip(r.probe_idx, r.match_obj)
            }
        assert out[False] == out[True]

    def test_indexed_plan_records_cache_hit(self, catalog):
        """Regression: the indexed-plan path read resident payloads via
        cache.get without recording a hit, skewing stats.hit_rate."""
        from repro.core import HybridCostModel, HybridPlanner

        planner = HybridPlanner(
            HybridCostModel(), objects_per_bucket=200, threshold_frac=0.02
        )
        eng = CrossMatchEngine(
            catalog, match_radius_rad=1e-3, hybrid=planner, cache_capacity=50
        )
        idx = np.arange(0, 400)
        # Pass 1: big queues -> scan plans establish residency.
        eng.submit(_probe_query(catalog, 0, idx))
        while eng.step() is not None:
            pass
        assert eng.cache.stats.misses > 0
        hits_before = eng.cache.stats.hits
        # Pass 2: tiny queues on the same buckets -> indexed plans on
        # resident payloads must now count as hits.
        eng.submit(_probe_query(catalog, 1, idx[:8]))
        while eng.step() is not None:
            pass
        assert eng.cache.stats.hits > hits_before

    def test_batching_shares_bucket_reads(self, catalog):
        """Two queries on the same region -> one bucket pass serves both."""
        eng = CrossMatchEngine(catalog, match_radius_rad=2e-3)
        idx = np.arange(100, 160)
        eng.submit(_probe_query(catalog, 0, idx))
        eng.submit(_probe_query(catalog, 1, idx))
        buckets_serviced = 0
        while eng.step() is not None:
            buckets_serviced += 1
        per_query = len({int(b) for q in (0, 1) for b in
                         [r.match_obj[0] for r in eng.results[q]]})
        assert buckets_serviced < 2 * max(per_query, 1) + 4
        assert eng.results[0] and eng.results[1]


class TestIntake:
    """Queries are decomposed by the partitioner's vectorised key lookup:
    no per-object range call at intake, every object tallied as ``keys``."""

    @pytest.mark.parametrize("sharded", [False, True])
    def test_submit_never_calls_the_range_lookup(self, catalog, monkeypatch, sharded):
        from repro.crossmatch import ShardedCrossMatch
        from repro.obs import Observability

        calls = []
        real = catalog.partitioner.buckets_for_range

        def counting(lo, hi):
            calls.append((lo, hi))
            return real(lo, hi)

        monkeypatch.setattr(catalog.partitioner, "buckets_for_range", counting)
        trace = make_trace(
            catalog,
            TraceConfig(n_queries=12, arrival_rate=2.0, objects_median=60, seed=3),
        )
        obs = Observability()
        if sharded:
            sx = ShardedCrossMatch(catalog, n_shards=2, cache_capacity=4, obs=obs)
            wm = sx.router
        else:
            sx = CrossMatchEngine(catalog, match_radius_rad=2e-3, obs=obs)
            wm = sx.wm
        for q in trace:
            sx.submit(q)
        assert calls == []
        assert wm.objects_by_keys == sum(q.n_objects for q in trace) > 0
        assert wm.objects_by_range == 0
        series = obs.snapshot()["metrics"]["liferaft_decompose_objects_total"]["series"]
        assert {s["labels"]["path"]: s["value"] for s in series} == {
            "keys": wm.objects_by_keys, "range": 0,
        }
        spanning = sum(
            int(np.sum(catalog.partitioner.bucket_of_keys(q.keys_hi)
                       > catalog.partitioner.bucket_of_keys(q.keys_lo)))
            for q in trace
        )
        assert wm.spanning_objects == spanning
