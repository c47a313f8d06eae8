"""The harness core: one cell of ``BENCHMARK.json``, served end to end.

A cell names a configuration (``bench/configs/<config>.json``, one
deployment) and a traffic mix (``bench/traffic/<mix>.json``); each metric
is read by ``bench/metrics/<name>.py``, or by the file named for the part
of the name before its first dot.  Nothing here changes for a new cell.

One run:

1. set-up: the catalog and the query stream from the seed, the served
   stack ``ServiceDaemon(CrossMatchHost(CrossMatchEngine(...)))``, a
   compile of every power-of-two shape the cell's traffic can reach, then
   the traffic itself for ``warmup_s`` so that queue and cache are in
   steady state;
2. the window: ``seconds`` of the same traffic, single-threaded: every
   query that is due is submitted through ``ServiceDaemon.submit``
   (journaled and fsync'd before the ack), then one scheduling round runs
   and completions are polled.  A query's response time runs from when it
   was due to the end of the round in which its last unit completed;
3. the drain: the traffic goes on until every query due in the window has
   completed (at most ``drain_s``);
4. the check: a sample of the queries due in the window, drawn from the
   seed and holding the one with the most work units, against the
   float64 reference (``bench/reference.py``).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import importlib.util
import json
import pathlib
import shutil
import tempfile
import time
from typing import Optional

import numpy as np

from . import reference as ref
from .gen.catalog import build_catalog
from .gen.trace import make_stream

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent

__all__ = [
    "Cell", "Run", "load_spec", "load_cell", "cell_metrics", "load_reader",
    "run_cell", "read_metrics",
]


class BenchError(RuntimeError):
    """A run that cannot give a result (the cell is misconfigured, the
    stream ran dry inside the window)."""


# ------------------------------------------------------------------ spec
@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    mix: dict
    chips: int
    spec: dict


def load_spec(root=ROOT) -> dict:
    return json.loads((pathlib.Path(root) / "BENCHMARK.json").read_text())


def load_cell(name: str, root=ROOT) -> Cell:
    spec = load_spec(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((pathlib.Path(root) / configs[w["config"]]["file"]).read_text())
    mix = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name, config, mix, int(w["chips"]), spec)


def cell_metrics(spec: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries a cell reports: those
    that list it, and those without a list whose end-to-end metric it
    reports."""
    e2e = [m for m in spec["end_to_end"] if cell in m.get("workloads", [cell])]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [
        m for m in spec["per_layer"]
        if (cell in m["workloads"] if "workloads" in m else m["moves"] in names)
    ]


def load_reader(name: str):
    """``read(run)`` of ``bench/metrics/<name>.py``, else of the file named
    for the part of ``name`` before its first dot."""
    for stem in (name, name.split(".")[0]):
        path = BENCH / "metrics" / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(f"bench_metric_{stem}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise BenchError(f"no reader for metric {name!r} under {BENCH / 'metrics'}")


# ------------------------------------------------------------------ probes
class Spans:
    """Host spans of the benchmark's own calls into the program, as
    (name, start, end) on ``perf_counter``; in a traced run each is also a
    ``TraceAnnotation`` named ``bench.<name>`` in the profiler's trace."""

    def __init__(self, annotate: bool = False) -> None:
        self.spans: list[tuple[str, float, float]] = []
        self.annotate = annotate

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(f"bench.{name}")
        t = time.perf_counter()
        with ann:
            yield
        self.spans.append((name, t, time.perf_counter()))


class CompileLog:
    """Times of XLA backend compiles, from JAX's compile events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.times: list[float] = []

    def _listen(self, event, duration, **_):
        if event == self.EVENT:
            self.times.append(time.perf_counter())

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listen)

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 <= t < t1)


# ------------------------------------------------------------------ program
def make_engine(config: dict, catalog):
    from repro.core import CostModel
    from repro.crossmatch import CrossMatchEngine

    c = config
    return CrossMatchEngine(
        catalog,
        cost_model=CostModel(T_b=float(c["T_b"]), T_m=float(c["T_m"])),
        cache_capacity=int(c["cache_capacity"]),
        match_radius_rad=float(c["match_radius_rad"]),
        mag_cut=float(c["mag_cut"]),
        use_pallas=bool(c["use_pallas"]),
        fuse_k=int(c["fuse_k"]),
        shared_plan=bool(c["shared_plan"]),
        share_width=int(c["share_width"]),
    )


def pred_of(config: dict):
    """(cos threshold, magnitude cut) of a query, as the configuration and
    the query's own predicate state them."""
    def pred(q):
        meta = q.meta or {}
        return (
            float(np.cos(float(meta.get("radius", config["match_radius_rad"])))),
            float(meta.get("mag_cut", config["mag_cut"])),
        )

    return pred


def _pow2(n: int, floor: int = 8) -> int:
    n = max(int(n), floor)
    return 1 << (n - 1).bit_length()


def reachable_shapes(config: dict, mix: dict, layout, queries) -> dict:
    """Every power-of-two (probe rows, bucket rows) shape of each kernel
    core the cell's traffic can reach.  A round joins at most ``fuse_k``
    buckets, so a query adds at most its units in its ``fuse_k`` fullest
    buckets; a call holds at most ``share_width`` queries (shared plan)
    or the loop's ``outstanding`` queries."""
    k_max = int(config["fuse_k"])
    per_query = []
    for q in queries:
        _, bucket = layout.units(q)
        per_query.append(int(np.sort(np.bincount(bucket))[::-1][:k_max].sum()))
    per_query.sort(reverse=True)
    shared = bool(config["shared_plan"])
    depth = int(config["share_width"]) if shared else mix.get("outstanding", len(queries))
    m_cap = _pow2(sum(per_query[:depth]))
    ms = [1 << p for p in range(3, m_cap.bit_length())]
    biggest = max(len(layout.rows(b)) for b in range(layout.n_buckets))
    ns = {k: _pow2(k * biggest) for k in range(1, k_max + 1)}
    if shared:
        cores = {"shared": sorted(set(ns.values()))}
    else:
        cores = {"single": [ns[1]],
                 "fused": sorted({ns[k] for k in range(2, k_max + 1)})}
    return {"m": ms, "cores": {c: n for c, n in cores.items() if n}}


def warm_shapes(engine, shapes: dict) -> int:
    """Compile (and keep in the persistent cache) every reachable shape,
    through the same entry points the engine calls.  Returns the number
    of calls made."""
    import jax
    from repro.kernels.crossmatch import ops

    key = "jax_persistent_cache_min_compile_time_secs"
    before = getattr(jax.config, key)
    jax.config.update(key, 0.0)
    calls = 0
    try:
        for core, ns in shapes["cores"].items():
            for n in ns:
                bucket = np.zeros((n, 3), np.float32)
                bucket[:, 2] = 1.0
                bseg = np.zeros(n, np.int32)
                for m in shapes["m"]:
                    probes = np.zeros((m, 3), np.float32)
                    probes[:, 2] = 1.0
                    pseg = np.zeros(m, np.int32)
                    if core == "single":
                        out = ops.crossmatch(bucket, probes, engine.cos_thr,
                                             use_pallas=engine.use_pallas)
                    elif core == "fused":
                        out = ops.crossmatch_fused(bucket, probes, bseg, pseg,
                                                   engine.cos_thr,
                                                   use_pallas=engine.use_pallas)
                    else:
                        thr = np.full(m, engine.cos_thr, np.float32)
                        out = ops.crossmatch_shared(bucket, probes, bseg, pseg, thr,
                                                    use_pallas=engine.use_pallas)
                    jax.block_until_ready(out)
                    calls += 1
    finally:
        jax.config.update(key, before)
    return calls


# ------------------------------------------------------------------ driver
class Driver:
    """The single-threaded client: submits what is due, runs one round,
    polls completions.  A closed loop keeps ``outstanding`` queries in
    flight (a completion makes the next query due at once); an open loop
    makes query i due ``sum(gaps[:i+1])`` seconds after the start.  Query i
    is the stream's query ``i % len(stream)`` under id i, so a system fast
    enough to finish the stream goes round it again."""

    def __init__(self, daemon, engine, stream, mix, spans: Spans, t0: float) -> None:
        self.daemon = daemon
        self.host = daemon.host
        self.queries = stream.queries
        self.closed = mix["loop"] == "closed"
        self.t0 = t0
        self.next = 0
        if self.closed:
            self.ready = collections.deque([t0] * int(mix["outstanding"]))
        else:
            self.gap_sum = np.concatenate([[0.0], np.cumsum(stream.gaps)])
        self.spans = spans
        self.outstanding: dict[int, float] = {}  # query id -> due
        self.submitted: dict[int, tuple[float, float]] = {}  # id -> (due, sent)
        self.done: dict[int, tuple[float, float]] = {}  # id -> (due, completed)
        self.rounds: list[tuple[float, tuple]] = []  # (end, ((bucket, rows),..))
        engine.loop.add_round_tap(self._tap)

    def _tap(self, outcome) -> None:
        self.rounds.append((
            time.perf_counter(),
            tuple((d.bucket_id, d.queue_size) for d in outcome.decisions),
        ))

    def query(self, i: int):
        """Query ``i`` of the traffic, not yet due."""
        return dataclasses.replace(self.queries[i % len(self.queries)], query_id=i)

    def next_due(self) -> Optional[float]:
        if self.closed:
            return self.ready[0] if self.ready else None
        laps, i = divmod(self.next, len(self.queries))
        return self.t0 + laps * self.gap_sum[-1] + float(self.gap_sum[i + 1])

    def _submit_due(self, now: float) -> None:
        while (due := self.next_due()) is not None and due <= now:
            q = dataclasses.replace(self.query(self.next), arrival_time=due - self.t0)
            with self.spans("submit"):
                self.daemon.submit(q)
            self.submitted[q.query_id] = (due, time.perf_counter())
            self.outstanding[q.query_id] = due
            self.next += 1
            if self.closed:
                self.ready.popleft()

    def _poll(self) -> None:
        ids = self.host.completed_ids()
        t = time.perf_counter()
        for qid in [q for q in self.outstanding if q in ids]:
            self.done[qid] = (self.outstanding.pop(qid), t)
            if self.closed:
                self.ready.append(t)

    def run(self, t_stop: float, wait_for=None) -> None:
        """Serve until ``t_stop``, or until every id in ``wait_for`` is
        done."""
        while (now := time.perf_counter()) < t_stop:
            if wait_for is not None and wait_for.issubset(self.done):
                return
            self._submit_due(now)
            if self.host.has_work():
                with self.spans("step"):
                    self.host.step()
                with self.spans("poll"):
                    self._poll()
                continue
            if self.outstanding:  # completed without a round of its own
                self._poll()
                continue
            due = self.next_due()
            with self.spans("idle"):
                time.sleep(max(0.0, min(due, t_stop) - time.perf_counter()))


# ------------------------------------------------------------------ run
@dataclasses.dataclass
class Run:
    """What a run measured, for the metric readers."""

    cell: Cell
    seed: int
    setup_s: float
    window: tuple[float, float]
    seconds: float
    rounds: list  # (end, ((bucket, rows), ...)) of the window's rounds
    spans: list  # (name, start, end) inside the window
    completed: int  # queries completed inside the window
    responses: list  # seconds from due to completion, queries due in the window
    counters: dict  # program counters, change over the window
    bucket_rows: dict  # objects per bucket
    trace: Optional[dict] = None  # bench/trace_reduce.py's reduction
    peak: Optional[dict] = None  # the device's peaks (bench/peaks.json)
    extra: dict = dataclasses.field(default_factory=dict)


def _counters(engine, compiles: CompileLog) -> dict:
    st = engine.cache.stats
    return {
        "cache_hits": st.hits,
        "cache_accesses": st.accesses,
        "device_dispatches": engine.loop.device_dispatches,
        "rounds": engine.loop.dispatches,
        "compiles": len(compiles.times),
    }


def _sample(rng, attempted: list, units, n: int) -> list:
    """``n`` ids drawn from ``attempted``, always holding the one with the
    most work units."""
    if not attempted:
        return []
    longest = max(attempted, key=lambda q: (units(q), q))
    rest = [q for q in attempted if q != longest]
    k = min(n - 1, len(rest))
    pick = rng.choice(len(rest), size=k, replace=False) if k > 0 else []
    return sorted([longest] + [rest[i] for i in pick])


def run_cell(
    cell: Cell,
    seed: int,
    seconds: float,
    *,
    t_start: Optional[float] = None,
    trace_dir=None,
    workers: int = 1,
    log=lambda msg: None,
) -> tuple[Run, dict]:
    """Serve one run of ``cell``; returns the measurements and the check."""
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    cfg, mix = cell.config, cell.mix
    catalog = build_catalog(cfg, seed, workers)
    stream = make_stream(mix, catalog.level, seed, workers)
    layout = ref.Layout(catalog.htm, cfg["objects_per_bucket"])
    log(f"data: {catalog.n_objects} objects in {catalog.n_buckets} buckets, "
        f"{len(stream.queries)} queries, {time.perf_counter() - t_start:.1f} s")

    from repro.serving import CrossMatchHost, ServiceDaemon

    engine = make_engine(cfg, catalog)
    shapes = reachable_shapes(cfg, mix, layout, stream.queries)
    t = time.perf_counter()
    n_warm = warm_shapes(engine, shapes)
    log(f"warm-up: {n_warm} shapes ({shapes['cores']}, m up to "
        f"{shapes['m'][-1]}) in {time.perf_counter() - t:.1f} s")

    journal = tempfile.mkdtemp(prefix="bench-journal-")
    spans = Spans(annotate=trace_dir is not None)
    daemon = ServiceDaemon(CrossMatchHost(engine), journal)
    try:
        with CompileLog() as compiles:
            t0 = time.perf_counter()
            driver = Driver(daemon, engine, stream, mix, spans, t0)
            driver.run(t0 + float(mix["warmup_s"]))
            if trace_dir is not None:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            c0 = _counters(engine, compiles)
            depth_start = len(driver.outstanding)
            w0 = time.perf_counter()
            w1 = w0 + float(seconds)
            if trace_dir is not None:
                with jax.profiler.TraceAnnotation("bench.window"):
                    driver.run(w1)
            else:
                driver.run(w1)
            c1 = _counters(engine, compiles)
            w_end = time.perf_counter()
            if trace_dir is not None:
                jax.profiler.stop_trace()
            depth_end = len(driver.outstanding)
            drift = engine.sim_clock - (w_end - t0)
            attempted = [q for q, (due, _) in driver.submitted.items() if w0 <= due < w1]
            driver.run(time.perf_counter() + float(mix["drain_s"]),
                       wait_for=set(attempted))
        counters = {k: c1[k] - c0[k] for k in c0}
        counters["compiles"] = compiles.between(w0, w1)
        stats = jax.devices()[0].memory_stats() or {}
        memory_peak = int(stats.get("peak_bytes_in_use", 0))
        rng = np.random.default_rng([seed, 0xC4EC])
        n_units = [len(layout.units(q)[0]) for q in stream.queries]
        sample = _sample(rng, [q for q in attempted if q in driver.done],
                         lambda i: n_units[i % len(n_units)], int(mix["check_queries"]))
        queries = [driver.query(q) for q in sample]
        results = {q: list(engine.results.get(q, [])) for q in sample}
    finally:
        daemon.close()
        engine.close()
        shutil.rmtree(journal, ignore_errors=True)
    late = [sent - due for due, sent in
            (driver.submitted[q] for q in attempted)]
    done_in = [q for q, (_, t) in driver.done.items() if w0 <= t < w1]
    responses = [driver.done[q][1] - driver.done[q][0] for q in attempted
                 if q in driver.done]
    run = Run(
        cell=cell, seed=seed, setup_s=w0 - t_start, window=(w0, w1),
        seconds=float(seconds),
        rounds=[r for r in driver.rounds if w0 <= r[0] < w1],
        spans=[s for s in spans.spans if w0 <= s[1] < w1],
        completed=len(done_in), responses=responses, counters=counters,
        bucket_rows={b: len(layout.rows(b)) for b in range(layout.n_buckets)},
        extra={
            "attempted": len(attempted),
            "failed": len([q for q in attempted if q not in driver.done]),
            "queue_depth_start": depth_start,
            "queue_depth_end": depth_end,
            "clock_drift_s": drift,
            "late_max_s": max(late, default=0.0),
            "late_mean_s": float(np.mean(late)) if late else 0.0,
            "memory_peak_bytes": memory_peak,
            "warm_shapes": n_warm,
        },
    )
    del engine, daemon, driver
    t = time.perf_counter()
    reference = ref.reference_join(catalog, layout, queries, pred_of(cfg))
    report = ref.check_results(layout, results, reference)
    report["reference_s"] = time.perf_counter() - t
    report["checked_queries"] = len(sample)
    report["unfinished"] = run.extra["failed"]
    report["checks"] = {
        "wrong": {"value": report["wrong"], "limit": 0},
        "dot_err": {"value": report["dot_err"], "limit": cfg["dot_err_limit"]},
        "unfinished": {"value": report["unfinished"], "limit": 0},
    }
    report["correct"] = bool(
        sample and all(c["value"] <= c["limit"] for c in report["checks"].values())
    )
    run.extra.update(catalog=catalog, layout=layout, sample=queries, results=results,
                     reference=reference)
    return run, report


def read_metrics(run: Run, entries: list[dict]) -> dict:
    """``{name: {"value", "unit"}}`` of every entry whose reader finds
    something to read."""
    out = {}
    for m in entries:
        v = load_reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
