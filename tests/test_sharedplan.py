"""Shared query plans: the query axis fused into one masked device call.

Three layers under test:

* kernel — ``crossmatch_shared`` (traced per-probe thresholds) must be
  bit-identical to the per-query ``crossmatch`` loop on the Pallas
  tile-skip path, and agree with it to the ulp bound below on the jnp
  reference path, across padded/sentinel edge shapes (property-based);
* compile bounding — K distinct predicates in one shared call must cost
  at most one ``jit_cache_size`` entry per pow2 shape pair, not K;
* control + engine — the AIMD ``share_width`` law, and the cross-match
  engine's one join path giving bit-equal results whatever its chunking:
  one call a round with ``shared_plan`` off, one per ``share_width``
  queries with it on.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.control import ControlConfig, ControlLoop, Telemetry
from repro.kernels.crossmatch import ops as cm_ops


def _unit_rows(rng, n):
    v = rng.normal(size=(n, 3))
    return v / np.linalg.norm(v, axis=1, keepdims=True)


def _make_case(rng, n_buckets, n_queries, rows_hi, n_empty):
    """Concatenated multi-bucket layout + per-query probe batches.

    ``n_empty`` trailing buckets get payload rows but no probes (the
    zero-query-bucket edge); queries draw heterogeneous thresholds.
    """
    sizes = [int(rng.integers(1, 30)) for _ in range(n_buckets + n_empty)]
    payloads = [_unit_rows(rng, s) for s in sizes]
    row_off = np.cumsum([0] + sizes[:-1])
    bucket_cat = np.concatenate(payloads)
    bseg = np.concatenate(
        [np.full(s, i, np.int64) for i, s in enumerate(sizes)]
    )
    queries = []
    for _ in range(n_queries):
        b = int(rng.integers(0, n_buckets))
        m = int(rng.integers(1, rows_hi + 1))
        # Probes near the bucket's own rows so thresholds actually bite.
        base = payloads[b][rng.integers(0, sizes[b], m)]
        probes = base + rng.normal(scale=2e-3, size=(m, 3))
        probes /= np.linalg.norm(probes, axis=1, keepdims=True)
        thr = float(rng.choice([0.95, 0.999, 0.999998]))
        queries.append((b, probes, thr))
    return bucket_cat, bseg, row_off, payloads, queries


def _bits(a):
    return np.ascontiguousarray(np.asarray(a, np.float32)).view(np.int32)


# XLA's CPU dot picks its kernel by operand shape: a (3, 8) x (8, 8) and a
# (3, 8) x (8, 64) dot of the same rows already round the sum of the three
# coordinate products differently.  The marker column adds exact zeros and
# plays no part.  So whenever two calls pad the bucket operand to different
# lengths (the shared call holds the whole concatenation, a per-query call
# one bucket; the jnp path is one dot, the Pallas path bn-wide tiles), one
# (probe, object) pair may come out an ulp or two apart.  Those calls are
# held to best_dot within _DOT_ULPS ulps, and to equal n_cand / best_idx
# wherever a shift of that size cannot move a pair across its threshold or
# swap the top two: _BAND is twice that shift, for dots in [0.5, 1) where
# the float32 ulp is 2**-24.  Calls with equal tiles stay bit-identical.
_DOT_ULPS = 2
_BAND = 2 * _DOT_ULPS * 2.0**-24


def _ulp_sensitive(probes, bucket, thr):
    """Probes whose n_cand or best_idx a _DOT_ULPS-sized shift of a dot
    can change, from an f64 join of the same float32 inputs."""
    d = (
        np.asarray(probes, np.float32).astype(np.float64)
        @ np.asarray(bucket, np.float32).astype(np.float64).T
    )
    thr = np.asarray(thr, np.float32).astype(np.float64).reshape(-1, 1)
    near_thr = (np.abs(d - thr) <= _BAND).any(axis=1)
    top2 = np.sort(d, axis=1)[:, -2:]
    tie = (top2[:, -1] - top2[:, 0] <= _BAND) & (d.shape[1] > 1)
    return near_thr | tie


def _assert_same_join(got, want, sensitive=None):
    """(best_idx, best_dot, n_cand) equal bit for bit, or, given the
    ulp-sensitive probes, to the bound above."""
    (g_idx, g_dot, g_cnt), (w_idx, w_dot, w_cnt) = (
        map(np.asarray, got), map(np.asarray, want)
    )
    if sensitive is None:
        np.testing.assert_array_equal(g_idx, w_idx)
        np.testing.assert_array_equal(_bits(g_dot), _bits(w_dot))
        np.testing.assert_array_equal(g_cnt, w_cnt)
        return
    np.testing.assert_array_max_ulp(
        np.asarray(g_dot, np.float32), np.asarray(w_dot, np.float32),
        maxulp=_DOT_ULPS,
    )
    firm = ~sensitive
    np.testing.assert_array_equal(g_idx[firm], w_idx[firm])
    np.testing.assert_array_equal(g_cnt[firm], w_cnt[firm])


class TestSharedKernel:
    @given(st.integers(1, 4), st.integers(1, 6), st.integers(1, 20),
           st.integers(0, 2))
    @settings(max_examples=8, deadline=None)
    def test_shared_equals_per_query_loop(
        self, n_buckets, n_queries, rows_hi, n_empty
    ):
        """One shared masked call == the per-query crossmatch loop across
        edge shapes: bit for bit on the Pallas path (both calls see the
        same 8x8 tiles), to the ulp bound on the jnp path (one dot each,
        of different padded lengths)."""
        seed = 100_000 * n_buckets + 10_000 * n_queries + 13 * rows_hi + n_empty
        rng = np.random.default_rng(seed)
        bucket_cat, bseg, row_off, payloads, queries = _make_case(
            rng, n_buckets, n_queries, rows_hi, n_empty
        )
        probes_cat = np.concatenate([p for _, p, _ in queries])
        pseg = np.concatenate(
            [np.full(len(p), b, np.int64) for b, p, _ in queries]
        )
        thr_row = np.concatenate(
            [np.full(len(p), t, np.float32) for _, p, t in queries]
        )
        for use_pallas in (False, True):
            kw = dict(use_pallas=use_pallas, bm=8, bn=8, interpret=True)
            s_idx, s_dot, s_cnt = map(np.asarray, cm_ops.crossmatch_shared(
                bucket_cat, probes_cat, bseg, pseg, thr_row, **kw
            ))
            at = 0
            for b, probes, thr in queries:
                want = cm_ops.crossmatch(payloads[b], probes, thr, **kw)
                sl = slice(at, at + len(probes))
                got = (s_idx[sl] - row_off[b], s_dot[sl], s_cnt[sl])
                sensitive = (
                    None if use_pallas
                    else _ulp_sensitive(probes, payloads[b], thr)
                )
                _assert_same_join(got, want, sensitive)
                at += len(probes)

    def test_single_query_single_probe(self):
        """Minimal shapes: one query, one probe row, one bucket row."""
        bucket = np.array([[1.0, 0.0, 0.0]])
        probes = np.array([[1.0, 0.0, 0.0]])
        idx, dot, cnt = cm_ops.crossmatch_shared(
            bucket, probes, np.zeros(1), np.zeros(1), np.array([0.99])
        )
        assert int(idx[0]) == 0 and int(cnt[0]) == 1
        assert float(dot[0]) == pytest.approx(1.0)

    def test_ref_vs_pallas_bit_identical(self):
        """The jnp and Pallas shared paths agree to the ulp bound (one dot
        against 8x8 tiles), and bit for bit at tiles as wide as the padded
        operands, where both paths run one dot of the same shape."""
        rng = np.random.default_rng(7)
        bucket_cat, bseg, row_off, payloads, queries = _make_case(
            rng, 3, 4, 12, 1
        )
        probes_cat = np.concatenate([p for _, p, _ in queries])
        pseg = np.concatenate(
            [np.full(len(p), b, np.int64) for b, p, _ in queries]
        )
        thr_row = np.concatenate(
            [np.full(len(p), t, np.float32) for _, p, t in queries]
        )
        r = cm_ops.crossmatch_shared(
            bucket_cat, probes_cat, bseg, pseg, thr_row, use_pallas=False
        )
        p = cm_ops.crossmatch_shared(
            bucket_cat, probes_cat, bseg, pseg, thr_row,
            use_pallas=True, bm=8, bn=8, interpret=True,
        )
        sensitive = np.concatenate(
            [_ulp_sensitive(pr, payloads[b], t) for b, pr, t in queries]
        )
        _assert_same_join(p, r, sensitive)
        whole = cm_ops.crossmatch_shared(
            bucket_cat, probes_cat, bseg, pseg, thr_row, use_pallas=True,
            bm=cm_ops._pow2_ceil(len(probes_cat)),
            bn=cm_ops._pow2_ceil(len(bucket_cat)), interpret=True,
        )
        _assert_same_join(whole, r)

    def test_shared_compiles_once_for_k_predicates(self):
        """K distinct thresholds at one pow2 shape pair: exactly one new
        compile-cache entry (the per-query static path would add K)."""
        rng = np.random.default_rng(11)
        bucket = _unit_rows(rng, 33)  # pads to 64: a fresh shape pair
        base = cm_ops.jit_cache_size()
        for k in range(6):  # 6 distinct predicates, same shapes
            probes = _unit_rows(rng, 9)  # pads to 16
            thr = np.full(9, 0.9 + 0.01 * k, np.float32)
            cm_ops.crossmatch_shared(
                bucket, probes, np.zeros(33), np.zeros(9), thr
            )
        assert cm_ops.jit_cache_size() == base + 1


class TestShareWidthLaw:
    def _tel(self, occ):
        return Telemetry(0.0, 1.0, 10, 10, 1, 0.0, 0.5, 0.5,
                        shared_occupancy=occ)

    def test_disabled_without_ceiling(self):
        loop = ControlLoop(ControlConfig(share_width_init=4))
        assert loop.update(self._tel(1.0)).share_width == 0

    def test_aimd_widen_narrow_clamp(self):
        cfg = ControlConfig(share_width_init=4, share_width_max=6,
                            share_occ_low=0.5, share_occ_high=0.95)
        loop = ControlLoop(cfg)
        assert loop.update(self._tel(1.0)).share_width == 5  # saturated: widen
        assert loop.update(self._tel(1.0)).share_width == 6
        assert loop.update(self._tel(1.0)).share_width == 6  # ceiling
        assert loop.update(self._tel(0.7)).share_width == 6  # in-band: hold
        assert loop.update(self._tel(0.1)).share_width == 5  # padding: narrow
        for _ in range(8):
            loop.update(self._tel(0.0))
        assert loop.update(self._tel(0.0)).share_width == 1  # floor


class TestEngineSharedPlan:
    def _setup(self, fuse_k=3, **eng_kw):
        from repro.crossmatch import (
            CrossMatchEngine, TraceConfig, make_catalog, make_trace,
        )

        catalog = make_catalog(
            n_objects=2_000, objects_per_bucket=100, htm_level=6, seed=17
        )
        trace = make_trace(
            catalog,
            TraceConfig(n_queries=14, arrival_rate=2.0, objects_median=40,
                        seed=19),
        )
        rng = np.random.default_rng(5)
        for q in trace:
            q.meta["radius"] = float(rng.choice([2e-3, 4e-3, 8e-3]))
            q.meta["mag_cut"] = float(rng.choice([23.0, 24.0, 25.0]))
        eng = CrossMatchEngine(
            catalog, match_radius_rad=4e-3, fuse_k=fuse_k, **eng_kw
        )
        return eng, trace

    @staticmethod
    def _assert_same_results(a, b):
        assert set(a) == set(b)
        for qid in a:
            ra = sorted(a[qid], key=lambda r: r.probe_idx.min() if len(r.probe_idx) else -1)
            rb = sorted(b[qid], key=lambda r: r.probe_idx.min() if len(r.probe_idx) else -1)
            assert len(ra) == len(rb)
            for x, y in zip(ra, rb):
                np.testing.assert_array_equal(x.probe_idx, y.probe_idx)
                np.testing.assert_array_equal(x.match_obj, y.match_obj)
                np.testing.assert_array_equal(_bits(x.best_dot), _bits(y.best_dot))
                np.testing.assert_array_equal(x.n_candidates, y.n_candidates)

    @staticmethod
    def _count_calls(eng):
        """Record (device calls, queries) as each round reports them."""
        notes = []
        note = eng.loop.note_device_dispatches

        def counted(n, *a, **kw):
            notes.append((n, kw.get("queries", 0)))
            note(n, *a, **kw)

        eng.loop.note_device_dispatches = counted
        return notes

    def test_shared_bit_equal_and_fewer_dispatches(self):
        eng_off, trace = self._setup(shared_plan=False)
        off_notes = self._count_calls(eng_off)
        res_off = eng_off.run(trace)
        eng_on, trace2 = self._setup(shared_plan=True, share_width=8)
        on_notes = self._count_calls(eng_on)
        res_on = eng_on.run(trace2)
        self._assert_same_results(res_off, res_on)
        # Off: one call a round.  On: one call per 8 of a round's queries.
        assert len(off_notes) == eng_off.loop.dispatches
        assert all(n == 1 for n, _ in off_notes)
        assert len(on_notes) == eng_on.loop.dispatches
        assert all(n == -(-q // 8) for n, q in on_notes)
        assert any(q > 8 for _, q in on_notes)  # some round takes two calls
        assert 0.0 < eng_on.summary()["shared_batch_occupancy"] <= 1.0

    @pytest.mark.parametrize("fuse_k", [1, 3])
    def test_heterogeneous_predicates_one_call_a_round(self, fuse_k):
        """Per-query radii and cuts with ``shared_plan`` off: every round is
        one device call, with the results of the chunked shared plan."""
        eng_off, trace = self._setup(fuse_k, shared_plan=False)
        res_off = eng_off.run(trace)
        eng_on, trace2 = self._setup(fuse_k, shared_plan=True, share_width=2)
        res_on = eng_on.run(trace2)
        self._assert_same_results(res_off, res_on)
        loop = eng_off.loop
        assert loop.dispatches > 0
        assert loop.device_dispatches == loop.shared_calls == loop.dispatches
        assert eng_on.loop.device_dispatches > eng_on.loop.dispatches

    def test_indexed_plans_change_no_result_or_decision(self):
        """A hybrid planner that sends resident buckets down the indexed
        plan at the scan's cost moves neither residency nor cost: results,
        hits, misses and the decision log equal the planner-less engine's,
        and each round is still one call."""
        import replay
        from repro.core import HybridCostModel, HybridPlanner, JoinPlan

        class IndexResident(HybridPlanner):
            indexed = 0

            def plan(self, queue_size, in_cache):
                self.indexed += in_cache
                return JoinPlan(
                    "indexed" if in_cache else "scan",
                    self.cost.batch_cost(queue_size, in_cache),
                    queue_size, in_cache,
                )

        runs = []
        for planner in (None, IndexResident(HybridCostModel(), 100)):
            eng, trace = self._setup(
                shared_plan=True, share_width=64, hybrid=planner,
                cache_capacity=6,
            )
            rec = replay.TraceRecorder()
            eng.loop.add_round_tap(rec)
            # Two waves over the same sky: the second finds buckets
            # resident, and the LRU order decides which.
            for wave in range(2):
                for q in trace:
                    eng.submit(dataclasses.replace(
                        q, query_id=q.query_id + 100 * wave,
                        arrival_time=eng.sim_clock,
                    ))
                while eng.step() is not None:
                    pass
            runs.append((eng, eng.results, rec.entries))
        (plain, res_plain, log_plain), (hyb, res_hyb, log_hyb) = runs
        assert hyb.hybrid.indexed > 0
        self._assert_same_results(res_plain, res_hyb)
        assert replay.diff_traces(log_plain, log_hyb) == []
        for a in ("hits", "misses"):
            assert getattr(hyb.cache.stats, a) == getattr(plain.cache.stats, a)
        assert hyb.loop.device_dispatches == hyb.loop.dispatches

    def test_width_one_chunking_still_bit_equal(self):
        """width < live queries: the executor chunks, results unchanged."""
        eng_off, trace = self._setup(shared_plan=False)
        res_off = eng_off.run(trace)
        eng_on, trace2 = self._setup(shared_plan=True, share_width=1)
        res_on = eng_on.run(trace2)
        self._assert_same_results(res_off, res_on)

    def test_width_exceeding_queries(self):
        """share_width far beyond the live query count: one chunk, low
        occupancy, same results."""
        eng_off, trace = self._setup(shared_plan=False)
        res_off = eng_off.run(trace)
        eng_on, trace2 = self._setup(shared_plan=True, share_width=64)
        res_on = eng_on.run(trace2)
        self._assert_same_results(res_off, res_on)
        assert eng_on.summary()["shared_batch_occupancy"] < 0.5

    def test_zero_query_bucket(self):
        """execute_shared on a bucket with no pending work: no crash, no
        device dispatch."""
        eng, _ = self._setup(shared_plan=True)
        before = eng.loop.device_dispatches
        eng.execute_shared([0])
        assert eng.loop.device_dispatches == before
