"""Observability layer (PR 10): metrics registry, round tracer, exporters.

The layer's whole contract is *taps-only*: it consumes the existing
side-channel taps and never touches the decision path.  The tests here
pin each clause of that contract:

  * golden bit-identity — every recorded scenario replays identically
    with obs ON (and the obs tap demonstrably fired);
  * histogram bucket edges — Prometheus ``le`` semantics, overflow,
    negative values, quantile clamping, ladder-mismatch errors;
  * snapshot determinism — two identical virtual-clocked runs produce
    *equal* snapshot dicts and Prometheus text;
  * Perfetto export — valid JSON, one named track per shard, and the
    steal arrows (instant + s/f flow pair) for the steal golden;
  * lazy import — with ``obs=`` left off, ``repro.obs`` is never
    imported (subprocess check);
  * daemon endpoints — journal append/fsync histograms, admission
    verdict counters, metrics_text/metrics_snapshot, and their empty
    obs-off fallbacks;
  * ControlExplain — vector changes carry the trigger-signal reason;
  * wall-clock phase spans — a served cross-match round, under each
    engine setting, is one span whose children are exactly its measured phases,
    inside the round and not overlapping; a submit is one span with a
    decompose child; both land in the profiler's trace as
    ``liferaft.*`` annotations; the host-to-device byte counter equals
    the padded operands' bytes;
  * shared-plan counters — calls, queries carried and distinct
    thresholds equal what ``execute_shared`` issued (every call of the
    one join path), and each ``launch`` span carries its call's queries
    and classes;
  * bucket-frame counters — misses copied into a recycled frame or a new
    one equal the engine's own counts.
"""
import json
import os
import pathlib
import subprocess
import sys
import tempfile

import numpy as np
import pytest

import replay
from repro.core import (
    AdmissionController,
    AdmissionQuota,
    AdmissionRejected,
)
from repro.obs import MetricsRegistry, Observability
from repro.serving import (
    AdapterSpec,
    CrossMatchHost,
    LifeRaftEngine,
    Request,
    ServeConfig,
    ServiceDaemon,
    ServingHost,
)

REPO = pathlib.Path(__file__).resolve().parent.parent

_MEMO = {}


def _obs_run(name):
    """One obs-ON run of a recorded scenario, shared across tests."""
    if name not in _MEMO:
        obs = Observability()
        entries = replay.SCENARIOS[name](obs=obs)
        _MEMO[name] = (obs, entries)
    return _MEMO[name]


# ------------------------------------------------------- golden bit-identity
@pytest.mark.parametrize("name", sorted(replay.SCENARIOS))
def test_goldens_bit_identical_with_obs_on(name):
    """The acceptance bar: observability must be a pure observer — the
    decision log with obs attached diffs empty against the golden."""
    obs, got = _obs_run(name)
    expect = replay.load_trace(replay.GOLDEN_DIR / f"{name}.json")
    divergence = replay.diff_traces(expect, got)
    assert not divergence, "\n".join(
        [f"obs-on decision log diverged from golden {name}:"] + divergence
    )
    # ... and obs was actually live, not silently detached.
    rounds = _obs_run(name)[0].snapshot()["metrics"]["liferaft_rounds_total"]
    assert sum(s["value"] for s in rounds["series"]) > 0


# ------------------------------------------------------------ histogram edges
class TestHistogramEdges:
    def test_le_semantics_overflow_and_negatives(self):
        reg = MetricsRegistry()
        h = reg.histogram("t_seconds", "test ladder", buckets=(1.0, 2.0, 5.0))
        for v in (0.5, 1.0, 1.5, 5.0, 7.0, -1.0):
            h.observe(v)
        cum = dict(h.cumulative())
        # le=1.0 holds 0.5, the exact bound 1.0, and the negative.
        assert cum[1.0] == 3
        assert cum[2.0] == 4
        assert cum[5.0] == 5  # 5.0 lands IN le=5.0, not overflow
        assert cum["+Inf"] == 6
        assert h.count == 6
        assert h.sum == pytest.approx(14.0)

    def test_quantiles_interpolate_and_clamp(self):
        reg = MetricsRegistry()
        h = reg.histogram("q_seconds", "", buckets=(1.0, 2.0, 5.0))
        for v in (0.5, 1.0, 1.5, 5.0, 7.0, -1.0):
            h.observe(v)
        # Median exhausts the first bucket exactly -> its upper bound.
        assert h.quantile(0.5) == pytest.approx(1.0)
        # Overflow mass clamps to the last finite bound.
        assert h.quantile(1.0) == pytest.approx(5.0)

    def test_empty_histogram_quantile_is_zero(self):
        reg = MetricsRegistry()
        assert reg.histogram("e_seconds", "").quantile(0.95) == 0.0

    def test_bucket_ladder_mismatch_raises(self):
        reg = MetricsRegistry()
        reg.histogram("m_seconds", "", buckets=(1.0, 2.0))
        with pytest.raises(ValueError, match="bucket ladder mismatch"):
            reg.histogram("m_seconds", "", buckets=(1.0, 3.0))

    def test_type_mismatch_raises(self):
        reg = MetricsRegistry()
        reg.histogram("x_seconds", "")
        with pytest.raises(ValueError, match="already registered"):
            reg.counter("x_seconds", "")

    def test_unsorted_bounds_rejected(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError, match="ascending"):
            reg.histogram("bad_seconds", "", buckets=(2.0, 1.0))


# ------------------------------------------------------- snapshot determinism
def test_virtual_clock_snapshot_is_run_to_run_identical():
    """Nothing wall-clock may enter the registry on virtual taps: a rerun
    of the same scenario yields an *equal* snapshot and Prometheus text."""
    fresh = Observability()
    replay.SCENARIOS["serving_adaptive"](obs=fresh)
    memo = _obs_run("serving_adaptive")[0]
    assert fresh.snapshot() == memo.snapshot()
    assert fresh.prometheus() == memo.prometheus()


# ------------------------------------------------------------ perfetto export
class TestPerfetto:
    def _doc(self):
        doc = _obs_run("sim_shard_steal")[0].perfetto()
        # must survive a JSON round-trip (the artifact CI uploads)
        return json.loads(json.dumps(doc))

    def test_one_named_track_per_shard(self):
        evs = self._doc()["traceEvents"]
        names = [
            e for e in evs
            if e["ph"] == "M" and e["name"] == "thread_name"
        ]
        assert sorted(e["tid"] for e in names) == [0, 1, 2, 3]
        assert {e["args"]["name"] for e in names} == {
            "shard-0", "shard-1", "shard-2", "shard-3"
        }
        spans = {e["tid"] for e in evs
                 if e["ph"] == "X" and e["name"] == "round"}
        assert spans == {0, 1, 2, 3}  # every shard dispatched rounds

    def test_steal_arrows_present_and_paired(self):
        evs = self._doc()["traceEvents"]
        instants = [e for e in evs
                    if e.get("cat") == "steal" and e["ph"] == "i"]
        starts = {e["id"]: e for e in evs
                  if e.get("cat") == "steal" and e["ph"] == "s"}
        finishes = [e for e in evs
                    if e.get("cat") == "steal" and e["ph"] == "f"]
        assert instants  # the steal golden must show migrations
        assert len(starts) == len(finishes) == len(instants)
        for f in finishes:  # arrow crosses tracks: victim != thief
            assert f["tid"] != starts[f["id"]]["tid"]

    def test_round_spans_are_ordered_per_track(self):
        evs = self._doc()["traceEvents"]
        by_track: dict = {}
        for e in evs:
            if e["ph"] == "X" and e["name"] == "round":
                by_track.setdefault(e["tid"], []).append(e["ts"])
        for ts in by_track.values():
            assert ts == sorted(ts)  # virtual clock: monotone per shard


# ----------------------------------------------------------------- lazy import
def test_obs_never_imported_when_disabled():
    """The obs-off hot path must not even import repro.obs."""
    code = (
        "import sys\n"
        "import replay\n"
        "replay.SCENARIOS['sim_raw_fused']()\n"
        "bad = sorted(m for m in sys.modules if m.startswith('repro.obs'))\n"
        "assert not bad, bad\n"
        "print('CLEAN')\n"
    )
    env = dict(os.environ, PYTHONPATH=f"src{os.pathsep}tests")
    res = subprocess.run(
        [sys.executable, "-c", code],
        cwd=str(REPO), capture_output=True, text=True, env=env,
    )
    assert res.returncode == 0, res.stderr
    assert "CLEAN" in res.stdout


# ------------------------------------------------------------ daemon endpoints
def _adapters(n=6):
    return [
        AdapterSpec(
            a,
            nbytes=(a + 1) * 1_000_000,
            tenant="interactive" if a % 2 else "batch",
        )
        for a in range(n)
    ]


def _reqs(n=12):
    return [
        Request(
            request_id=i,
            adapter_id=(i * 5) % 6,
            arrival_time=0.01 * i,
            prompt_len=32 + (i % 7) * 16,
            max_new_tokens=32,
        )
        for i in range(n)
    ]


class TestDaemonEndpoints:
    def test_journal_admission_and_round_metrics(self, tmp_path):
        adm = AdmissionController({"batch": AdmissionQuota(max_queue_depth=2)})
        obs = Observability()
        eng = LifeRaftEngine(
            _adapters(), ServeConfig(adapter_slots=3, fuse_k=2, adaptive=True),
            obs=obs,
        )
        d = ServiceDaemon(ServingHost(eng), tmp_path / "j",
                          admission=adm, obs=obs)
        accepted = rejected = 0
        for r in _reqs():  # no pumping: the batch tenant must hit quota
            try:
                d.submit(r)
                accepted += 1
            except AdmissionRejected:
                rejected += 1
        assert rejected > 0
        d.pump()
        snap = d.metrics_snapshot()
        m = snap["metrics"]
        # Every synced submit ack paid an append AND an fsync barrier.
        appends = m["liferaft_journal_append_seconds"]["series"][0]
        fsyncs = m["liferaft_journal_fsync_seconds"]["series"][0]
        assert appends["count"] >= accepted + rejected
        assert fsyncs["count"] >= accepted + rejected
        assert fsyncs["sum"] > 0.0
        # Admission verdicts balance the submissions.
        verdicts = {
            (s["labels"]["tenant"], s["labels"]["verdict"]): s["value"]
            for s in m["liferaft_admission_total"]["series"]
        }
        assert sum(verdicts.values()) == accepted + rejected
        assert verdicts.get(("batch", "rejected"), 0) == rejected
        reasons = m["liferaft_admission_rejected_total"]["series"]
        assert {s["labels"]["reason"] for s in reasons} == {"queue_depth"}
        # The engine shared the same Observability: rounds were recorded.
        assert m["liferaft_rounds_total"]["series"][0]["value"] > 0
        # Text exposition serves the same registry.
        text = d.metrics_text()
        assert "# TYPE liferaft_admission_total counter" in text
        assert 'verdict="rejected"' in text
        assert "liferaft_journal_fsync_seconds_bucket" in text

    def test_obs_off_endpoints_are_empty(self, tmp_path):
        eng = LifeRaftEngine(
            _adapters(), ServeConfig(adapter_slots=3, fuse_k=2)
        )
        d = ServiceDaemon(ServingHost(eng), tmp_path / "j")
        assert d.metrics_text() == ""
        assert d.metrics_snapshot() == {}


# ------------------------------------------------------------- control explain
def test_control_explain_names_the_trigger_signal():
    obs, _ = _obs_run("serving_adaptive")
    events = obs.snapshot()["control_explain"]
    assert events  # the adaptive scenario moves the vector
    for e in events:
        assert {"track", "clock", "field", "from", "to", "message"} <= set(e)
        assert e["from"] != e["to"]
    fields = {e["field"] for e in events}
    assert "alpha" in fields
    # The message leads with the field's trigger signal (docs/adaptive.md).
    alpha_msgs = [e["message"] for e in events if e["field"] == "alpha"]
    assert any("saturation" in m for m in alpha_msgs)


# ------------------------------------------------------- wall-clock phase spans
ROUND_PHASES = {
    "select", "fetch", "gather", "launch", "readback", "route", "complete",
}
# Call pattern -> CrossMatchEngine settings that take it: one bucket,
# three buckets, per-query predicates in one call, in chunks of two.
PATHS = {
    "single": dict(fuse_k=1),
    "fused": dict(fuse_k=3),
    "per_predicate": dict(fuse_k=2),
    "shared": dict(fuse_k=2, shared_plan=True, share_width=2),
}
_SERVED = {}


def _catalog():
    from repro.crossmatch import make_catalog

    if "catalog" not in _SERVED:
        _SERVED["catalog"] = make_catalog(
            n_objects=2_000, objects_per_bucket=100, htm_level=6, seed=17
        )
    return _SERVED["catalog"]


def _served(path):
    """A CrossMatchEngine behind a ServiceDaemon, sharing one
    Observability, that served a small trace down one executor path:
    (obs, engine, rounds served, queries submitted)."""
    if path not in _SERVED:
        from repro.crossmatch import CrossMatchEngine, TraceConfig, make_trace

        catalog = _catalog()
        trace = make_trace(
            catalog,
            TraceConfig(n_queries=8, arrival_rate=2.0, objects_median=40,
                        seed=19),
        )
        if path in ("per_predicate", "shared"):
            rng = np.random.default_rng(5)
            for q in trace:
                q.meta["radius"] = float(rng.choice([2e-3, 4e-3, 8e-3]))
                q.meta["mag_cut"] = float(rng.choice([23.0, 24.0]))
        obs = Observability()
        eng = CrossMatchEngine(
            catalog, match_radius_rad=4e-3, obs=obs, **PATHS[path]
        )
        with tempfile.TemporaryDirectory() as journal:
            daemon = ServiceDaemon(CrossMatchHost(eng), journal, obs=obs)
            for q in trace:
                daemon.submit(q)
            n_rounds = daemon.pump()
            daemon.close()
        _SERVED[path] = (obs, eng, n_rounds, len(trace))
    return _SERVED[path]


def _phase_series(obs, phase):
    m = obs.snapshot()["metrics"]["liferaft_phase_seconds"]["series"]
    return next(s for s in m if s["labels"]["phase"] == phase)


@pytest.mark.parametrize("path", sorted(PATHS))
class TestPhaseSpans:
    def test_round_children_are_the_phases(self, path):
        obs, eng, n_rounds, _ = _served(path)
        rounds = [s for s in obs.tracer.spans if s[1] == "round"]
        assert len(rounds) == n_rounds == eng.loop.dispatches > 0
        for track, _, t0, dur, children, args in rounds:
            assert track == 0
            assert {c[0] for c in children} == ROUND_PHASES
            assert children[0][0] == "select"
            assert children[-1][0] == "complete"
            assert len(args["buckets"]) >= 1
            end = 0.0
            for name, off, cdur in children:
                assert cdur >= 0.0
                assert off >= end, f"{name} overlaps the child before it"
                end = off + cdur
            assert end <= dur

    def test_one_select_per_round(self, path):
        obs, eng, n_rounds, _ = _served(path)
        assert _phase_series(obs, "select")["count"] == n_rounds
        assert _phase_series(obs, "complete")["count"] == n_rounds
        m = obs.snapshot()["metrics"]
        wall = m["liferaft_round_wall_seconds"]["series"][0]
        assert wall["count"] == n_rounds
        # The rounds' children are the phase observations of the rounds.
        spans = [s for s in obs.tracer.spans if s[1] == "round"]
        for phase in ROUND_PHASES:
            total = sum(c[2] for s in spans for c in s[4] if c[0] == phase)
            assert _phase_series(obs, phase)["sum"] == pytest.approx(total)
        assert "liferaft_round_select_seconds" not in m

    def test_submit_spans_and_fsync(self, path):
        obs, _, _, n_queries = _served(path)
        subs = [s for s in obs.tracer.spans if s[1] == "submit"]
        assert len(subs) == n_queries
        for _, _, _, dur, children, args in subs:
            assert args["key"].startswith("q-")
            assert [c[0] for c in children] == ["decompose"]
            assert 0.0 <= children[0][1] and sum(children[0][1:]) <= dur
        assert _phase_series(obs, "decompose")["count"] == n_queries
        m = obs.snapshot()["metrics"]
        fsync = m["liferaft_journal_fsync_seconds"]["series"][0]
        assert fsync["count"] == n_queries and fsync["sum"] > 0.0

    def test_perfetto_shows_the_phases(self, path):
        obs = _served(path)[0]
        evs = json.loads(json.dumps(obs.perfetto()))["traceEvents"]
        rounds = [e for e in evs if e["ph"] == "X" and e["name"] == "round"]
        kids = [e for e in evs if e["ph"] == "X" and e["cat"] == "round"
                and e["name"] != "round"]
        assert {e["name"] for e in kids} <= ROUND_PHASES
        assert {"select", "launch", "complete"} <= {e["name"] for e in kids}
        for k in kids:
            assert any(r["ts"] <= k["ts"] and k["ts"] + k["dur"]
                       <= r["ts"] + r["dur"] + 1e-3 for r in rounds)
        assert any(e["ph"] == "X" and e["name"] == "decompose" for e in evs)


def test_virtual_round_children_partition_the_round():
    """On the cost model's clock the children are laid end to end from
    the round's start and fill it."""
    obs, _ = _obs_run("sim_prefetch")
    rounds = [s for s in obs.tracer.spans if s[1] == "round"]
    assert rounds
    for _, _, t0, dur, children, _ in rounds:
        assert children[0][1] == 0.0
        for (_, off, cdur), nxt in zip(children, children[1:] + ((None, None, None),)):
            if nxt[1] is not None:
                assert nxt[1] == pytest.approx(off + cdur)
        assert sum(c[2] for c in children) == pytest.approx(dur)


def _pow2(n):
    return 1 << (max(n, 8) - 1).bit_length()


@pytest.mark.parametrize("core", ["single", "fused", "shared"])
def test_h2d_bytes_of_one_known_call(core):
    """``ops.h2d_bytes`` grows by the nbytes of the padded operands every
    wrapper hands to the one jitted core: (rows, 8) f32 coordinates, and
    f32 segment ids and thresholds, each padded to a power of two."""
    from repro.kernels.crossmatch import ops

    n, m = 37, 5
    rng = np.random.default_rng(0)
    bucket = rng.normal(size=(n, 3)).astype(np.float32)
    probes = bucket[:m]
    bseg, pseg = np.zeros(n, np.int32), np.zeros(m, np.int32)
    before = ops.h2d_bytes()
    if core == "single":
        ops.crossmatch(bucket, probes, 0.5)
    elif core == "fused":
        ops.crossmatch_fused(bucket, probes, bseg, pseg, 0.5)
    else:
        ops.crossmatch_shared(bucket, probes, bseg, pseg,
                              np.full(m, 0.5, np.float32))
    want = (_pow2(n) * 9 + _pow2(m) * 10) * 4
    assert ops.h2d_bytes() - before == want


def test_h2d_counter_equals_the_operands_of_a_served_call():
    """One query of five objects inside one bucket: one round, one
    single-bucket call, and ``liferaft_h2d_bytes_total`` holds exactly
    the bytes of its padded operands: coordinates, segment ids and
    thresholds."""
    from repro.core.workload import Query
    from repro.crossmatch import CrossMatchEngine

    catalog = _catalog()
    b = 3
    payload = catalog.store.read(b)
    rows = slice(40, 45)
    keys = payload["htm"][rows]
    q = Query(query_id=0, arrival_time=0.0, keys_lo=keys, keys_hi=keys,
              payload={"positions": payload["positions"][rows]})
    obs = Observability()
    eng = CrossMatchEngine(catalog, match_radius_rad=4e-3, obs=obs)
    eng.run([q])
    assert eng.loop.dispatches == 1
    n = len(payload["positions"])
    want = (_pow2(n) * 9 + _pow2(5) * 10) * 4
    m = obs.snapshot()["metrics"]["liferaft_h2d_bytes_total"]["series"][0]
    assert m["value"] == want


def test_bucket_frame_counters_equal_the_engine_counts():
    """``liferaft_bucket_frames_total`` splits the misses by the frame they
    were copied into; it equals the engine's plain counts, they sum to the
    cache's misses, and a small cache recycles most frames."""
    from repro.crossmatch import CrossMatchEngine, TraceConfig, make_trace

    catalog = _catalog()
    trace = make_trace(
        catalog,
        TraceConfig(n_queries=30, arrival_rate=2.0, objects_median=40, seed=19),
    )
    obs = Observability()
    eng = CrossMatchEngine(
        catalog, match_radius_rad=4e-3, cache_capacity=3, fuse_k=2, obs=obs
    )
    for q in trace:
        eng.sim_clock = max(eng.sim_clock, q.arrival_time)
        eng.submit(q)
        eng.step()
    eng.run([])
    series = obs.snapshot()["metrics"]["liferaft_bucket_frames_total"]["series"]
    got = {s["labels"]["outcome"]: s["value"] for s in series}
    assert got == {
        "reused": eng.frames_reused, "allocated": eng.frames_allocated,
    }
    assert eng.frames_reused + eng.frames_allocated == eng.cache.stats.misses
    assert eng.frames_allocated <= 3 + 2 < eng.frames_reused


def test_decompose_counters_equal_the_manager_tallies():
    """``liferaft_decompose_objects_total`` and
    ``liferaft_decompose_spanning_objects_total`` mirror the workload
    manager's tallies; the engine decomposes every object by key."""
    from repro.crossmatch import CrossMatchEngine, TraceConfig, make_trace

    catalog = _catalog()
    trace = make_trace(
        catalog,
        TraceConfig(n_queries=20, arrival_rate=2.0, objects_median=40, seed=23),
    )
    obs = Observability()
    eng = CrossMatchEngine(catalog, match_radius_rad=4e-3, obs=obs)
    for q in trace:
        eng.submit(q)
    m = obs.snapshot()["metrics"]
    got = {
        s["labels"]["path"]: s["value"]
        for s in m["liferaft_decompose_objects_total"]["series"]
    }
    assert got == {"keys": sum(q.n_objects for q in trace), "range": 0}
    assert got["keys"] == eng.wm.objects_by_keys > 0
    (spanning,) = m["liferaft_decompose_spanning_objects_total"]["series"]
    assert spanning["value"] == eng.wm.spanning_objects


def test_phase_spans_land_in_the_profiler_trace(tmp_path):
    """The spans are ``liferaft.*`` TraceAnnotations in the profiler's own
    trace: each round holds its select/launch/complete, each submit its
    decompose, and the round carries its index and buckets."""
    import jax
    from jax.profiler import ProfileData
    from repro.crossmatch import CrossMatchEngine, TraceConfig, make_trace

    catalog = _catalog()
    trace = make_trace(catalog, TraceConfig(
        n_queries=3, arrival_rate=2.0, objects_median=20, seed=23))
    obs = Observability()
    eng = CrossMatchEngine(catalog, match_radius_rad=4e-3, fuse_k=2, obs=obs)
    daemon = ServiceDaemon(CrossMatchHost(eng), tmp_path / "j", obs=obs)
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        for q in trace:
            daemon.submit(q)
        daemon.pump()
    finally:
        jax.profiler.stop_trace()
        daemon.close()
    (path,) = (tmp_path / "trace").glob("**/*.xplane.pb")
    events = [
        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
        for plane in ProfileData.from_file(str(path)).planes
        for line in plane.lines
        for ev in line.events
        if ev.name.startswith("liferaft.")
    ]
    names = {e[0] for e in events}
    assert {f"liferaft.{p}" for p in ROUND_PHASES} <= names
    assert {"liferaft.round", "liferaft.submit", "liferaft.decompose"} <= names
    rounds = [e for e in events if e[0] == "liferaft.round"]
    assert len(rounds) == eng.loop.dispatches
    assert sorted(r[3]["round"] for r in rounds) == list(range(len(rounds)))
    assert all("buckets" in r[3] for r in rounds)
    for name, s, e, _ in events:
        parent = "liferaft.submit" if name == "liferaft.decompose" else (
            "liferaft.round" if name[9:] in ROUND_PHASES else None)
        if parent is not None:
            assert any(p[0] == parent and p[1] <= s and e <= p[2]
                       for p in events), name


# ------------------------------------------------------- shared-plan counters
SHARED_COUNTERS = ("calls", "queries", "predicate_classes")


def _counted_shared_serve(path, monkeypatch, obs, profile_dir=None):
    """Serve a small trace down ``path`` with ``ops.crossmatch_shared`` and
    ``execute_shared`` wrapped to tally what the shared plan issues: each
    call, its distinct thresholds, and each round's distinct queries (one
    chunk carries each).  Returns (engine, tally)."""
    from repro.crossmatch import CrossMatchEngine, TraceConfig, make_trace
    from repro.kernels.crossmatch import ops

    issued = dict.fromkeys(SHARED_COUNTERS, 0)
    real_kernel = ops.crossmatch_shared

    def kernel(bucket, probes, bseg, pseg, thr, **kw):
        issued["calls"] += 1
        issued["predicate_classes"] += len(np.unique(np.asarray(thr, np.float32)))
        return real_kernel(bucket, probes, bseg, pseg, thr, **kw)

    monkeypatch.setattr(ops, "crossmatch_shared", kernel)
    catalog = _catalog()
    trace = make_trace(catalog, TraceConfig(
        n_queries=10, arrival_rate=2.0, objects_median=40, seed=29))
    if path == "shared":
        rng = np.random.default_rng(7)
        for q in trace:
            q.meta["radius"] = float(rng.choice([2e-3, 4e-3, 8e-3]))
            q.meta["mag_cut"] = float(rng.choice([23.0, 24.0]))
    eng = CrossMatchEngine(catalog, match_radius_rad=4e-3, obs=obs, **PATHS[path])
    real_execute = eng.execute_shared

    def execute_shared(decisions, vector=None):
        wm = eng.wm
        issued["queries"] += len({
            u.query_id for d in decisions
            for u in wm.queue(d.bucket_id).units + wm.queue(d.bucket_id).spilled_units
        })
        return real_execute(decisions, vector)

    eng.execute_shared = execute_shared
    with tempfile.TemporaryDirectory() as journal:
        daemon = ServiceDaemon(CrossMatchHost(eng), journal, obs=obs)
        if profile_dir is not None:
            import jax

            jax.profiler.start_trace(str(profile_dir))
        try:
            for q in trace:
                daemon.submit(q)
            daemon.pump()
        finally:
            if profile_dir is not None:
                jax.profiler.stop_trace()
            daemon.close()
    return eng, issued


@pytest.mark.parametrize("path", ["shared", "fused"])
def test_shared_plan_counters_equal_what_execute_shared_issued(path, monkeypatch):
    obs = Observability()
    eng, issued = _counted_shared_serve(path, monkeypatch, obs)
    metrics = obs.snapshot()["metrics"]
    got = {
        c: metrics[f"liferaft_shared_{c}_total"]["series"][0]["value"]
        for c in SHARED_COUNTERS
    }
    assert got == issued
    loop = eng.loop
    assert (loop.shared_calls, loop.shared_queries, loop.shared_classes) == tuple(
        issued[c] for c in SHARED_COUNTERS)
    if path == "shared":
        # Chunks of two queries with three radii: some calls hold two
        # thresholds, and every query rides in one call a round.
        assert 0 < issued["calls"] < issued["predicate_classes"]
        assert issued["calls"] <= issued["queries"] <= 2 * issued["calls"]
        assert loop.device_dispatches == issued["calls"]
    else:
        # Shared plan off: every query of a round rides in its one call.
        assert issued["calls"] == loop.device_dispatches == loop.dispatches > 0
    text = obs.prometheus()
    for c in SHARED_COUNTERS:
        assert f"liferaft_shared_{c}_total" in text


def test_shared_launch_spans_carry_queries_and_classes(monkeypatch, tmp_path):
    """Each ``liferaft.launch`` annotation of the shared plan names its
    call's queries and distinct thresholds; summed, they are the
    counters."""
    from jax.profiler import ProfileData

    obs = Observability()
    eng, issued = _counted_shared_serve("shared", monkeypatch, obs,
                                        profile_dir=tmp_path / "trace")
    (path,) = (tmp_path / "trace").glob("**/*.xplane.pb")
    launches = [
        dict(ev.stats)
        for plane in ProfileData.from_file(str(path)).planes
        for line in plane.lines
        for ev in line.events
        if ev.name == "liferaft.launch"
    ]
    assert len(launches) == issued["calls"] > 0
    assert sum(int(a["queries"]) for a in launches) == issued["queries"]
    assert sum(int(a["classes"]) for a in launches) == issued["predicate_classes"]
    assert all(1 <= int(a["classes"]) <= int(a["queries"]) <= 2 for a in launches)
