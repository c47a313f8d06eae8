"""Durable service tier: write-ahead journal, crash recovery, admission
control — plus the shard/control-tier bugfix regressions that ride along.

Covers:
  * ``Journal`` mechanics: segment rotation, restart-opens-new-segment,
    torn-tail tolerance, mid-journal corruption detection
  * ``ServiceDaemon`` recovery: golden-vs-recovered decision bit-identity
    for the single serving engine, the sharded serving engine (including
    steal overrides), and the cross-match engine; idempotent
    resubmission; RecoveryError on journal/engine disagreement
  * per-tenant admission control: deterministic 429s, journaled and
    replayed bit-identically
  * the truncation property (satellite 5): replayed state == live state
    at every captured truncation point of a recorded run
  * satellite bugfix regressions: adapter-slot remainder conservation,
    waterfill zero-demand slack, dryrun perf_counter, cross-match drain
    thread fault propagation
  * the kill -9 gate, via ``benchmarks/smoke_recovery`` in a subprocess
"""
import os
import pathlib
import shutil
import subprocess
import sys
import tempfile

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    AdmissionController,
    AdmissionQuota,
    AdmissionRejected,
    Journal,
    JournalCorrupt,
    StealConfig,
    diff_entries,
    split_slots,
    waterfill,
)
from repro.crossmatch import (
    CrossMatchEngine,
    ShardedCrossMatch,
    TraceConfig,
    make_catalog,
    make_trace,
)
from repro.serving import (
    AdapterSpec,
    CrossMatchHost,
    LifeRaftEngine,
    RecoveryError,
    Request,
    ServeConfig,
    ServiceDaemon,
    ServingHost,
    ShardedServingEngine,
)

REPO = pathlib.Path(__file__).resolve().parent.parent


# ----------------------------------------------------------- scenario helpers
def _adapters(n=6):
    return [
        AdapterSpec(
            a,
            nbytes=(a + 1) * 1_000_000,
            tenant="interactive" if a % 2 else "batch",
        )
        for a in range(n)
    ]


def _trace(n=40, n_adapters=6):
    return [
        Request(
            request_id=i,
            adapter_id=(i * 5) % n_adapters,
            arrival_time=0.01 * i,
            prompt_len=32 + (i % 7) * 16,
            max_new_tokens=32,
        )
        for i in range(n)
    ]


_CFG = ServeConfig(adapter_slots=3, fuse_k=2, adaptive=True)


def _serving_daemon(journal_dir, cfg=_CFG, **daemon_kw):
    return ServiceDaemon(
        ServingHost(LifeRaftEngine(_adapters(), cfg)), journal_dir, **daemon_kw
    )


def _drive(daemon, items):
    for it in items:
        daemon.pump(until=it.arrival_time)
        daemon.submit(it)
    daemon.pump()


_MEMO = {}


def _memo(key, builder):
    """Module-lifetime cache for expensive recorded runs; plain dict
    rather than fixtures so ``@given`` tests (whose drawn arguments are
    passed positionally by the hypothesis stub) can share them too."""
    if key not in _MEMO:
        _MEMO[key] = builder()
    return _MEMO[key]


def _catalog():
    return _memo(
        "catalog",
        lambda: make_catalog(n_objects=3000, objects_per_bucket=200, seed=5),
    )


@pytest.fixture(scope="module")
def small_catalog():
    return _catalog()


def _xmatch_trace(catalog, n=12):
    return make_trace(
        catalog,
        TraceConfig(n_queries=n, seed=9, objects_median=60, arrival_rate=2.0),
    )


# ================================================================== journal
class TestJournal:
    def test_rotation_and_replay_order(self, tmp_path):
        j = Journal(tmp_path / "j", segment_bytes=256)
        recs = [{"type": "entry", "entry": {"i": i, "pad": "x" * 40}}
                for i in range(50)]
        for r in recs:
            j.append(r)
        j.close()
        assert len(j.segments()) > 1  # rotation actually happened
        assert Journal(tmp_path / "j").replay() == recs

    def test_restart_opens_new_segment(self, tmp_path):
        j1 = Journal(tmp_path / "j")
        j1.append({"type": "entry", "entry": {"i": 0}})
        j1.close()
        j2 = Journal(tmp_path / "j")
        j2.append({"type": "entry", "entry": {"i": 1}})
        j2.close()
        assert len(j2.segments()) == 2
        assert [r["entry"]["i"] for r in j2.replay()] == [0, 1]

    def test_torn_tail_dropped(self, tmp_path):
        j = Journal(tmp_path / "j")
        j.append({"type": "submit", "key": "a", "item": {}})
        j.append({"type": "submit", "key": "b", "item": {}})
        j.close()
        seg = j.segments()[-1]
        with open(seg, "a", encoding="utf-8") as fh:
            fh.write('{"type":"submit","key":"c","it')  # torn mid-write
        recs = Journal(tmp_path / "j").replay()
        assert [r["key"] for r in recs] == ["a", "b"]

    def test_mid_journal_corruption_raises(self, tmp_path):
        j = Journal(tmp_path / "j")
        for k in ("a", "b", "c"):
            j.append({"type": "submit", "key": k, "item": {}})
        j.close()
        seg = j.segments()[0]
        lines = seg.read_text().splitlines()
        lines[1] = lines[1][: len(lines[1]) // 2]  # damage a middle line
        seg.write_text("\n".join(lines) + "\n")
        with pytest.raises(JournalCorrupt):
            Journal(tmp_path / "j").replay()

    def test_codec_shared_with_golden_harness(self):
        # The tentpole's schema-unification claim: the golden-trace
        # recorder and the journal literally share one codec.
        sys.path.insert(0, str(REPO / "tests"))
        try:
            import replay as golden_harness
        finally:
            sys.path.pop(0)
        from repro.core import journal

        assert golden_harness.encode_outcome is journal.encode_outcome
        assert golden_harness.diff_traces is journal.diff_entries


# ================================================================ admission
class TestAdmission:
    def test_queue_depth_quota(self):
        ctl = AdmissionController({"batch": AdmissionQuota(max_queue_depth=3)})
        ctl.check("batch", 2, 0.0)  # 2 + 1 <= 3
        with pytest.raises(AdmissionRejected) as ei:
            ctl.check("batch", 3, 0.0)
        assert ei.value.reason == "queue_depth"
        assert ei.value.status == 429
        assert ei.value.observed == 3.0 and ei.value.limit == 3.0
        ctl.check("interactive", 1000, 0.0)  # unlisted tenant: unlimited

    def test_pending_bytes_quota_and_default(self):
        ctl = AdmissionController(
            default=AdmissionQuota(max_pending_bytes=100.0)
        )
        ctl.check("anyone", 0, 60.0, add_bytes=40.0)
        with pytest.raises(AdmissionRejected) as ei:
            ctl.check("anyone", 0, 60.0, add_bytes=41.0)
        assert ei.value.reason == "pending_bytes"

    def test_daemon_rejects_journaled_and_replayed(self, tmp_path):
        adm = AdmissionController(
            {"batch": AdmissionQuota(max_queue_depth=2)}
        )
        d = _serving_daemon(tmp_path / "j", admission=adm)
        rejected = []
        for r in _trace(10):  # no pumping: queues only grow
            try:
                d.submit(r)
            except AdmissionRejected:
                rejected.append(r.request_id)
        assert rejected  # the batch tenant hit its quota
        # cached rejection re-raised on resubmit, identical fields
        dup = [r for r in _trace(10) if r.request_id == rejected[0]][0]
        with pytest.raises(AdmissionRejected):
            d.submit(dup)
        d.close()
        # replay reproduces every disposition without re-checking quota
        d2 = _serving_daemon(tmp_path / "j", admission=adm)
        assert sorted(
            int(k.rsplit("-", 1)[1]) for k in d2.rejected
        ) == sorted(rejected)
        for rid in rejected:
            assert d2.disposition(f"req-{rid}") == "rejected"
            with pytest.raises(AdmissionRejected):
                d2.submit([r for r in _trace(10) if r.request_id == rid][0])
        d2.close()

    def test_retry_after_drain_admits(self, tmp_path):
        adm = AdmissionController(
            {"batch": AdmissionQuota(max_queue_depth=2)}
        )
        d = _serving_daemon(tmp_path / "j", admission=adm)
        reqs = [r for r in _trace(12) if r.adapter_id % 2 == 0]  # batch only
        got_reject = None
        for r in reqs:
            try:
                d.submit(r)
            except AdmissionRejected:
                got_reject = r
                break
        assert got_reject is not None
        d.pump()  # drain: quota headroom restored
        fresh = [
            r for r in _trace(12) if r.request_id == got_reject.request_id
        ][0]
        assert d.submit(fresh, retry=True)["status"] == "acked"
        d.close()
        # the later submit record supersedes the journaled 429 on replay
        d2 = _serving_daemon(tmp_path / "j", admission=adm)
        assert d2.disposition(f"req-{got_reject.request_id}") == "acked"
        d2.close()


# ======================================================== daemon recovery
class TestDaemonRecovery:
    def _golden_crash_recover(self, make_daemon, items, crash_after, tmp):
        """Golden run; same driver crashed after ``crash_after`` submits
        (abandoned without close — the in-process stand-in for kill -9);
        recover and finish; return (golden, recovered)."""
        golden = make_daemon(tmp / "golden")
        _drive(golden, items())
        golden.close()
        crashed = make_daemon(tmp / "crashed")
        for it in items()[:crash_after]:
            crashed.pump(until=it.arrival_time)
            crashed.submit(it)
        del crashed  # no close: tail past the last fsync may tear
        recovered = make_daemon(tmp / "crashed")
        _drive(recovered, items())
        recovered.close()
        return golden, recovered

    def test_single_engine_bit_identical(self, tmp_path):
        golden, rec = self._golden_crash_recover(
            _serving_daemon, _trace, 20, tmp_path
        )
        assert diff_entries(golden.entries, rec.entries) == []
        assert rec.completed() == golden.completed()
        assert len(rec.completed()) == len(_trace())

    def test_sharded_engine_bit_identical(self, tmp_path):
        def make(d):
            eng = ShardedServingEngine(
                _adapters(), _CFG, n_shards=3,
                steal=StealConfig(low_water_bytes=50.0),
            )
            return ServiceDaemon(ServingHost(eng), d)

        golden, rec = self._golden_crash_recover(make, _trace, 25, tmp_path)
        assert diff_entries(golden.entries, rec.entries) == []
        assert rec.completed() == golden.completed()
        # recovered shard state (incl. any steal overrides) matches a
        # never-crashed run exactly
        assert rec.state_fingerprint() == golden.state_fingerprint()

    def test_crossmatch_engine_bit_identical(self, tmp_path, small_catalog):
        def make(d):
            eng = CrossMatchEngine(small_catalog, cache_capacity=4, fuse_k=2)
            return ServiceDaemon(CrossMatchHost(eng), d)

        items = lambda: _xmatch_trace(small_catalog)  # noqa: E731
        golden, rec = self._golden_crash_recover(make, items, 7, tmp_path)
        assert diff_entries(golden.entries, rec.entries) == []
        assert rec.completed() == golden.completed()
        assert len(rec.completed()) == 12

    def test_idempotent_resubmission(self, tmp_path):
        d = _serving_daemon(tmp_path / "j")
        r = _trace(1)[0]
        assert d.submit(r)["status"] == "acked"
        assert d.submit(_trace(1)[0])["status"] == "duplicate"
        before = d.journal.appended
        d.submit(_trace(1)[0])
        assert d.journal.appended == before  # duplicates are not journaled
        d.close()

    def test_ack_is_write_ahead(self, tmp_path):
        d = _serving_daemon(tmp_path / "j")
        d.submit(_trace(1)[0])
        # the record is already durable on disk, pre-pump, pre-close
        recs = Journal(tmp_path / "j").replay()
        assert [r["type"] for r in recs] == ["submit"]
        assert recs[0]["key"] == "req-0"
        d.close()

    def test_recovery_refuses_divergent_engine(self, tmp_path):
        d = _serving_daemon(tmp_path / "j")
        _drive(d, _trace(10))
        d.close()
        # 'recover' under a different config: decisions cannot match
        other = ServeConfig(adapter_slots=3, fuse_k=2, alpha=0.9)
        with pytest.raises(RecoveryError):
            _serving_daemon(tmp_path / "j", cfg=other)

    def test_recovery_tolerates_torn_tail(self, tmp_path):
        d = _serving_daemon(tmp_path / "j")
        for r in _trace(8):
            d.pump(until=r.arrival_time)
            d.submit(r)
        d.journal._fh.write('{"type":"entry","ent')  # crash mid-append
        d.journal._fh.flush()
        del d
        rec = _serving_daemon(tmp_path / "j")
        _drive(rec, _trace(8))
        rec.close()
        golden = _serving_daemon(tmp_path / "g")
        _drive(golden, _trace(8))
        golden.close()
        assert diff_entries(golden.entries, rec.entries) == []


# ================================================= truncation property (#5)
def _record_run(make_daemon, items):
    """Drive a daemon one operation at a time, capturing the engine state
    fingerprint at every journal record count reached."""
    dirpath = tempfile.mkdtemp(prefix="rec-")
    try:
        d = make_daemon(dirpath)
        points = {d.journal.appended: d.state_fingerprint()}

        def settle(until):
            while d.host.has_work() and (
                until is None or d.host.clock() < until
            ):
                if d.host.step() is None:
                    break
                points[d.journal.appended] = d.state_fingerprint()

        for it in items:
            settle(it.arrival_time)
            d.submit(it)
            points[d.journal.appended] = d.state_fingerprint()
        settle(None)
        d.close()
        return d.journal.replay(), points
    finally:
        shutil.rmtree(dirpath)


def _check_truncation(make_daemon, records, points, t):
    """Copy the first ``t`` journal records into a fresh directory, recover
    a daemon there, and assert its state equals the live run's state at
    that point."""
    tmp = tempfile.mkdtemp(prefix="truncation-")
    try:
        trunc = Journal(tmp)
        for rec in records[:t]:
            trunc.append(rec)
        trunc.close()
        d = make_daemon(tmp)
        fp = d.state_fingerprint()
        d.close()
        assert fp == points[t], f"state diverged at truncation point {t}"
    finally:
        shutil.rmtree(tmp)


def _recorded_serving():
    return _memo(
        "rec-serving", lambda: _record_run(_serving_daemon, _trace(24))
    )


def _make_sharded_daemon(d):
    eng = ShardedServingEngine(
        _adapters(), _CFG, n_shards=2,
        steal=StealConfig(low_water_bytes=1e4, min_victim_queues=1),
    )
    return ServiceDaemon(ServingHost(eng), d)


def _recorded_sharded():
    def build():
        # skew arrivals onto shard 1's adapters so shard 0 runs dry
        # and steals — the recorded run must exercise steal overrides
        reqs = [
            Request(request_id=i, adapter_id=4 + (i % 2) if i > 2 else 0,
                    arrival_time=0.01 * i, prompt_len=64, max_new_tokens=32)
            for i in range(20)
        ]
        records, points = _record_run(_make_sharded_daemon, reqs)
        assert any(
            "steal" in r["entry"] for r in records if r["type"] == "entry"
        ), "scenario must exercise steal overrides"
        return records, points

    return _memo("rec-sharded", build)


def _make_xmatch_daemon(d):
    eng = CrossMatchEngine(_catalog(), cache_capacity=4, fuse_k=2)
    return ServiceDaemon(CrossMatchHost(eng), d)


def _recorded_xmatch():
    return _memo(
        "rec-xmatch",
        lambda: _record_run(_make_xmatch_daemon, _xmatch_trace(_catalog())),
    )


class TestTruncationProperty:
    @settings(max_examples=8, deadline=None)
    @given(st.integers(min_value=0, max_value=10_000))
    def test_serving_state_matches_at_any_truncation(self, draw):
        records, points = _recorded_serving()
        counts = sorted(points)
        _check_truncation(
            _serving_daemon, records, points, counts[draw % len(counts)]
        )

    @settings(max_examples=6, deadline=None)
    @given(st.integers(min_value=0, max_value=10_000))
    def test_sharded_state_matches_at_any_truncation(self, draw):
        records, points = _recorded_sharded()
        counts = sorted(points)
        _check_truncation(
            _make_sharded_daemon, records, points, counts[draw % len(counts)]
        )

    @settings(max_examples=5, deadline=None)
    @given(st.integers(min_value=0, max_value=10_000))
    def test_crossmatch_state_matches_at_any_truncation(self, draw):
        records, points = _recorded_xmatch()
        counts = sorted(points)
        _check_truncation(
            _make_xmatch_daemon, records, points, counts[draw % len(counts)]
        )


# ==================================================== satellite regressions
class TestSlotSplit:
    """Satellite 1: ``slots // S`` dropped the remainder."""

    @settings(max_examples=40, deadline=None)
    @given(st.integers(min_value=1, max_value=64),
           st.integers(min_value=1, max_value=8))
    def test_split_conserves_and_balances(self, total, n):
        parts = split_slots(total, n)
        assert len(parts) == n
        assert all(p >= 1 for p in parts)
        if total >= n:
            assert sum(parts) == total  # conservation — the bug
            assert max(parts) - min(parts) <= 1
        else:
            assert parts == [1] * n  # floor-at-1 inflation only

    @pytest.mark.parametrize("n_shards", [1, 2, 3, 4])
    def test_sharded_serving_conserves_aggregate_slots(self, n_shards):
        cfg = ServeConfig(adapter_slots=6)
        eng = ShardedServingEngine(_adapters(), cfg, n_shards=n_shards)
        assert (
            sum(e.cache.capacity for e in eng.engines) == cfg.adapter_slots
        )
        # remainder goes to the lowest shard ids
        caps = [e.cache.capacity for e in eng.engines]
        assert caps == sorted(caps, reverse=True)

    def test_sharded_crossmatch_conserves_cache_slots(self, small_catalog):
        sx = ShardedCrossMatch(small_catalog, n_shards=3, cache_capacity=7)
        assert sum(e.cache.capacity for e in sx.engines) == 7


class TestWaterfill:
    """Satellite 2: final slack was spread over zero-demand parties."""

    def test_zero_demand_party_gets_nothing(self):
        grants = waterfill({"a": 10.0, "b": 0.0, "c": 5.0}, {}, 30.0)
        assert grants["b"] == 0.0
        assert sum(grants.values()) == pytest.approx(30.0)
        # slack beyond total demand lands on the demanders
        assert grants["a"] > 10.0 and grants["c"] > 5.0

    @settings(max_examples=30, deadline=None)
    @given(
        st.lists(st.floats(min_value=0.0, max_value=100.0),
                 min_size=1, max_size=6),
        st.floats(min_value=0.0, max_value=500.0),
    )
    def test_conservation_and_no_free_grants(self, demands, budget):
        demand = {f"t{i}": d for i, d in enumerate(demands)}
        grants = waterfill(demand, {}, budget)
        assert sum(grants.values()) == pytest.approx(budget)
        if any(d > 0.0 for d in demands):
            for t, d in demand.items():
                if d == 0.0:
                    assert grants[t] == 0.0


def test_dryrun_times_with_perf_counter():
    """Satellite 3: lowering/compile timings must use the monotonic
    clock, matching trainer.py."""
    import inspect

    from repro.launch import dryrun

    src = inspect.getsource(dryrun.run_cell)
    assert "time.time()" not in src
    assert "time.perf_counter()" in src


class TestDrainFault:
    """Satellite 4: a drain thread dying must surface at join, with the
    originating shard id, instead of hanging or passing silently."""

    def test_store_fault_propagates_with_shard_id(self, small_catalog):
        sx = ShardedCrossMatch(small_catalog, n_shards=2, cache_capacity=4)
        boom = RuntimeError("injected store fault")
        real_read = small_catalog.store.read
        calls = []

        def failing_read(bucket_id, out=None):
            calls.append(bucket_id)
            if len(calls) >= 2:
                raise boom
            return real_read(bucket_id, out=out)

        small_catalog.store.read = failing_read
        try:
            with pytest.raises(RuntimeError, match=r"shard \d+ drain thread died"):
                sx.run(_xmatch_trace(small_catalog, n=8))
        finally:
            small_catalog.store.read = real_read
        assert sx._drain_errors
        sid, exc = sx._drain_errors[0]
        assert exc is boom
        assert sx._abort.is_set()

    def test_error_chains_original_exception(self, small_catalog):
        sx = ShardedCrossMatch(small_catalog, n_shards=2, cache_capacity=4)
        real_read = small_catalog.store.read
        small_catalog.store.read = lambda b, out=None: (_ for _ in ()).throw(
            ValueError("disk on fire")
        )
        try:
            with pytest.raises(RuntimeError) as ei:
                sx.run(_xmatch_trace(small_catalog, n=8))
        finally:
            small_catalog.store.read = real_read
        assert isinstance(ei.value.__cause__, ValueError)


# ================================================= the kill -9 gate (CI smoke)
def test_kill9_recovery_gate(tmp_path):
    """Headline gate: SIGKILL a journaling daemon mid-flood, recover, and
    require every acked query to complete with decisions bit-identical to
    an uninterrupted run.  Runs the CI smoke in-subprocess with a short
    trace."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    proc = subprocess.run(
        [
            sys.executable, "-m", "benchmarks.smoke_recovery",
            "--dir", str(tmp_path / "journal"), "--n", "60",
            "--throttle", "0.02",
        ],
        cwd=str(REPO),
        env=env,
        capture_output=True,
        text=True,
        timeout=280,
    )
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
