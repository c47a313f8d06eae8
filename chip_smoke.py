#!/usr/bin/env python3
"""Smoke run of the LifeRaft cross-match service on one TPU chip.

    python chip_smoke.py [--seed N]

Builds a 2,000,000-object catalog at the paper's bucket width (200 buckets
of 10,000 objects, HTM level 10) and a 200-query SkyQuery-style trace, all
from ``--seed``, and drives the trace through the served path,
``ServiceDaemon(CrossMatchHost(CrossMatchEngine(...)))`` with ``submit``
and ``pump``, in four phases:

1. the default engine: the jnp join;
2. ``use_pallas=True``: the Pallas kernel, one bucket a call;
3. ``use_pallas=True, fuse_k=4``: the Pallas kernel, four buckets a call;
4. ``use_pallas=True, shared_plan=True`` with a per-query radius: the
   Pallas kernel, ``share_width`` queries a call with per-row thresholds.

Phases 2-4 take the first 60 queries.  Every phase is checked against a
float64 brute-force join (:func:`reference_join`) that shares no code with
the kernels, cache, scheduler or routing.  One line per phase reports
queries completed, matched probes, probes inside the threshold band,
disagreements, compile and run seconds (smoke times, not benchmark
metrics) and whether the phase's compiled program holds a Mosaic kernel.
The last line is ``{"ok": true, "device": {...}}``.

Any platform but ``tpu`` is refused with a non-zero exit, and so is any
phase that disagrees with the reference or leaves a query incomplete.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import CostModel  # noqa: E402
from repro.crossmatch import (  # noqa: E402
    CrossMatchEngine,
    TraceConfig,
    make_catalog,
    make_trace,
)
from repro.kernels.crossmatch import ops as cm_ops  # noqa: E402
from repro.serving import CrossMatchHost, ServiceDaemon  # noqa: E402

# The engine joins float32 coordinates; the reference joins the same
# coordinates in float64.  DELTA covers the float32 arithmetic between
# them: each dot sums three products of unit-vector components (at most
# 3 * 2**-24 rounding), and a TPU's HIGHEST precision builds the float32
# product from six bf16 passes, dropping terms of up to 2 * 2**-24 and
# rounding each partial sum.  8 * 2**-24, four float32 ulps at 1.0, bounds
# that with room: on a TPU v5e at seed 0 the largest error over ~200,000
# work units is 2.2 * 2**-24.  A decision that a dot this close to its
# threshold (or to the runner-up) could flip is counted "in band", not
# checked.
DELTA = 8 * 2.0**-24

RADIUS = 1e-3  # match radius in rad, the engine's default
MAG_CUT = 24.0  # the engine's default magnitude cut
RADII = (5e-4, 1e-3, 2e-3, 4e-3)  # phase 4's per-query radii, in rad
PHASES = (
    ("jnp", {}),
    ("pallas-single", {"use_pallas": True}),
    ("pallas-fused", {"use_pallas": True, "fuse_k": 4}),
    ("pallas-shared", {"use_pallas": True, "shared_plan": True}),
)


def build_workload(
    seed: int,
    n_objects: int = 2_000_000,
    objects_per_bucket: int = 10_000,
    htm_level: int = 10,
    n_queries: int = 200,
    objects_median: int = TraceConfig.objects_median,
):
    """The catalog and the query trace, both made from ``seed``."""
    catalog = make_catalog(
        n_objects=n_objects,
        objects_per_bucket=objects_per_bucket,
        htm_level=htm_level,
        seed=seed,
    )
    trace = make_trace(
        catalog,
        TraceConfig(
            n_queries=n_queries, objects_median=objects_median, seed=seed + 1
        ),
    )
    return catalog, trace


def with_radii(queries, seed: int):
    """Copies of ``queries`` that each carry a seeded ``meta['radius']``."""
    rng = np.random.default_rng(seed + 2)
    return [
        dataclasses.replace(
            q, meta={**q.meta, "radius": float(rng.choice(RADII))}
        )
        for q in queries
    ]


# ------------------------------------------------------------------ reference
@dataclasses.dataclass
class Reference:
    """Per (query, probe, bucket) work unit of the trace's decomposition,
    sorted by ``key``."""

    key: np.ndarray  # packed (query, probe, bucket), see _pack
    best_row: np.ndarray  # catalog row of the nearest object
    best_dot: np.ndarray  # its float64 dot
    n_above: np.ndarray  # pairs with dot > thr + DELTA
    n_band: np.ndarray  # pairs with |dot - thr| <= DELTA
    unique_best: np.ndarray  # no runner-up within DELTA of the best
    matched: np.ndarray  # best >= thr and the best object passes the cut
    firm: np.ndarray  # matched status cannot flip within DELTA


def _pack(qid, probe, bucket):
    return (
        (np.asarray(qid, np.int64) << 40)
        | (np.asarray(probe, np.int64) << 16)
        | np.asarray(bucket, np.int64)
    )


def _bucket_layout(catalog, objects_per_bucket):
    """Equal-count buckets over the HTM-sorted catalog: the first key of
    each bucket, and the rows of each bucket in key order."""
    order = np.argsort(catalog.htm, kind="stable")
    n_buckets = -(-len(order) // objects_per_bucket)
    first_keys = catalog.htm[order][np.arange(n_buckets) * objects_per_bucket]
    return order, first_keys


def reference_join(catalog, objects_per_bucket, queries, thr_of, chunk=1024):
    """Brute-force float64 join of every probe against every object of each
    bucket its key range covers.  ``thr_of(query)`` is the query's cos
    threshold; it is rounded to float32, as the engine compares in
    float32."""
    order, first_keys = _bucket_layout(catalog, objects_per_bucket)
    n_buckets = len(first_keys)

    def bucket_of(keys):
        b = np.searchsorted(first_keys, keys, side="right") - 1
        return np.clip(b, 0, n_buckets - 1)

    units_q, units_p, units_b, units_thr, units_xyz = [], [], [], [], []
    for q in queries:
        lo, hi = bucket_of(q.keys_lo), bucket_of(q.keys_hi)
        span = hi - lo + 1
        probe = np.repeat(np.arange(len(lo)), span)
        step = np.arange(len(probe)) - np.repeat(np.cumsum(span) - span, span)
        units_q.append(np.full(len(probe), q.query_id))
        units_p.append(probe)
        units_b.append(lo[probe] + step)
        units_thr.append(np.full(len(probe), np.float32(thr_of(q))))
        units_xyz.append(q.payload["positions"][probe])
    qid = np.concatenate(units_q)
    probe = np.concatenate(units_p)
    bucket = np.concatenate(units_b)
    thr = np.concatenate(units_thr).astype(np.float64)
    xyz = np.concatenate(units_xyz).astype(np.float32).astype(np.float64)
    pos = catalog.positions.astype(np.float32).astype(np.float64)
    mag_ok = catalog.mags <= MAG_CUT

    n = len(qid)
    best_row = np.zeros(n, np.int64)
    best_dot = np.zeros(n)
    n_above = np.zeros(n, np.int64)
    n_band = np.zeros(n, np.int64)
    unique_best = np.zeros(n, bool)
    mag_split = np.zeros(n, bool)
    for b in np.unique(bucket):
        rows = order[b * objects_per_bucket : (b + 1) * objects_per_bucket]
        objs = pos[rows].T
        ok = mag_ok[rows]
        units = np.nonzero(bucket == b)[0]
        for at in range(0, len(units), chunk):
            u = units[at : at + chunk]
            t = xyz[u] @ objs
            t -= thr[u, None]  # dot - thr
            j = np.argmax(t, axis=1)
            top = t[np.arange(len(u)), j]
            n_above[u] = np.count_nonzero(t > DELTA, axis=1)
            n_band[u] = np.count_nonzero(t >= -DELTA, axis=1) - n_above[u]
            tied = t >= (top - DELTA)[:, None]
            n_tied = np.count_nonzero(tied, axis=1)
            for r in np.nonzero(n_tied > 1)[0]:
                cut = ok[tied[r]]
                mag_split[u[r]] = cut.any() and not cut.all()
            best_row[u] = rows[j]
            best_dot[u] = top + thr[u]
            unique_best[u] = n_tied == 1
    matched = (best_dot >= thr) & mag_ok[best_row]
    firm = (np.abs(best_dot - thr) > DELTA) & ~mag_split
    key = _pack(qid, probe, bucket)
    s = np.argsort(key)
    return Reference(
        key[s], best_row[s], best_dot[s], n_above[s], n_band[s],
        unique_best[s], matched[s], firm[s],
    )


def check_results(catalog, objects_per_bucket, results, ref: Reference):
    """Compare the engine's routed matches with the reference.

    The engine reports matched probes only.  Per work unit: the matched
    status must agree wherever it is firm; a reported probe's ``n_cand``
    must agree where no pair lies in the band, its object where the best
    is unique within DELTA, and its ``best_dot`` everywhere to DELTA.
    """
    order, _ = _bucket_layout(catalog, objects_per_bucket)
    bucket_of_row = np.empty(len(order), np.int64)
    bucket_of_row[order] = np.arange(len(order)) // objects_per_bucket
    recs = [r for rs in results.values() for r in rs]
    if recs:
        key = np.concatenate(
            [_pack(r.query_id, r.probe_idx, bucket_of_row[r.match_obj])
             for r in recs]
        )
        obj = np.concatenate([r.match_obj for r in recs])
        dot = np.concatenate([r.best_dot for r in recs]).astype(np.float64)
        cnt = np.concatenate([r.n_candidates for r in recs])
    else:
        key = obj = cnt = np.zeros(0, np.int64)
        dot = np.zeros(0)
    at = np.minimum(np.searchsorted(ref.key, key), max(len(ref.key) - 1, 0))
    known = (ref.key[at] == key) if len(ref.key) else np.zeros(len(key), bool)
    reported = np.zeros(len(ref.key), bool)
    reported[at[known]] = True
    at = at[known]
    err = np.abs(dot[known] - ref.best_dot[at])
    bad = {
        "unknown_or_duplicate": int(
            (~known).sum() + known.sum() - len(np.unique(at))
        ),
        "status": int((ref.firm & (reported != ref.matched)).sum()),
        "n_cand": int(
            ((ref.n_band[at] == 0) & (cnt[known] != ref.n_above[at])).sum()
        ),
        "best_idx": int(
            (ref.unique_best[at] & (obj[known] != ref.best_row[at])).sum()
        ),
        "best_dot": int((err > DELTA).sum()),
    }
    return {
        "matched": int(known.sum()),
        "in_band": int((~ref.firm).sum()),
        "disagreements": sum(bad.values()),
        "by_check": bad,
        "max_dot_err": float(err.max()) if len(err) else 0.0,
    }


# ------------------------------------------------------------------ phases
@contextlib.contextmanager
def compile_clock():
    """Seconds JAX spends tracing, lowering and compiling inside the block,
    read from its compile-duration events: ``with compile_clock() as c:``
    then ``c[0]``."""
    total = [0.0]

    def listen(event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            total[0] += duration

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield total
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def holds_mosaic(engine_kw) -> bool:
    """Whether the core a phase's engine calls compiles to a Mosaic kernel
    (``tpu_custom_call``), checked at the smallest engine shape."""
    use_pallas = engine_kw.get("use_pallas", False)
    interpret = cm_ops._resolve_interpret(None, use_pallas)
    x = np.zeros((8, 8), np.float32)
    v = np.zeros(8, np.float32)
    lowered = cm_ops._crossmatch_shared_jit.lower(
        x, x, v, v, v, use_pallas, 128, 512, interpret
    )
    return "tpu_custom_call" in lowered.compile().as_text()


def run_phase(catalog, queries, engine_kw, journal_dir):
    """Serve ``queries`` through the daemon: pump to each arrival, submit
    it (journaled and acked), then drain.  Returns the engine's routed
    results, the completed query ids and the compile and run seconds."""
    engine = CrossMatchEngine(
        catalog,
        cost_model=CostModel(T_b=1.2, T_m=0.13e-3),
        cache_capacity=20,
        match_radius_rad=RADIUS,
        mag_cut=MAG_CUT,
        **engine_kw,
    )
    daemon = ServiceDaemon(CrossMatchHost(engine), journal_dir)
    try:
        with compile_clock() as compile_s:
            t0 = time.perf_counter()
            for q in sorted(queries, key=lambda q: q.arrival_time):
                daemon.pump(until=q.arrival_time)
                daemon.submit(q)
            daemon.pump()
            wall = time.perf_counter() - t0
        completed = daemon.completed()
    finally:
        daemon.close()
        engine.close()
    return engine.results, completed, compile_s[0], wall - compile_s[0]


def smoke_phase(name, engine_kw, catalog, objects_per_bucket, queries, ref):
    """One phase end to end: serve, check, report."""
    with tempfile.TemporaryDirectory(prefix="chip_smoke_journal_") as jd:
        results, completed, compile_s, run_s = run_phase(
            catalog, queries, engine_kw, jd
        )
    report = check_results(catalog, objects_per_bucket, results, ref)
    report.update(
        phase=name,
        queries=len(queries),
        completed=len(completed & {q.query_id for q in queries}),
        compile_s=compile_s,
        run_s=run_s,
        mosaic=holds_mosaic(engine_kw),
    )
    report["ok"] = (
        report["disagreements"] == 0
        and report["completed"] == report["queries"]
        and report["mosaic"] == (engine_kw.get("use_pallas", False)
                                 and jax.default_backend() == "tpu")
    )
    return report


def run_smoke(catalog, trace, objects_per_bucket, seed, n_kernel_queries=60,
              log=print):
    """All four phases; returns their reports."""
    t0 = time.perf_counter()
    ref_all = reference_join(
        catalog, objects_per_bucket, trace, lambda q: np.cos(RADIUS)
    )
    head = trace[:n_kernel_queries]
    radii = with_radii(head, seed)
    ref_radii = reference_join(
        catalog, objects_per_bucket, radii,
        lambda q: np.cos(q.meta["radius"]),
    )
    log(f"reference: {len(ref_all.key)} + {len(ref_radii.key)} work units, "
        f"{time.perf_counter() - t0:.1f} s")
    head_ids = np.array([q.query_id for q in head], np.int64)
    in_head = np.isin(ref_all.key >> 40, head_ids)
    ref_head = Reference(*(getattr(ref_all, f.name)[in_head]
                           for f in dataclasses.fields(Reference)))
    plan = [
        (PHASES[0], trace, ref_all),
        (PHASES[1], head, ref_head),
        (PHASES[2], head, ref_head),
        (PHASES[3], radii, ref_radii),
    ]
    reports = []
    for (name, kw), queries, ref in plan:
        rep = smoke_phase(name, kw, catalog, objects_per_bucket, queries, ref)
        log(
            f"phase {name}: queries {rep['completed']}/{rep['queries']} "
            f"matched {rep['matched']} in_band {rep['in_band']} "
            f"disagreements {rep['disagreements']} {rep['by_check']} "
            f"max|dot-ref| {rep['max_dot_err']!r} "
            f"compile_s {rep['compile_s']!r} run_s {rep['run_s']!r} "
            f"mosaic {rep['mosaic']} ok {rep['ok']}"
        )
        reports.append(rep)
    return reports


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}",
              file=sys.stderr)
        return 1
    def log(msg):
        print(msg, flush=True)

    log(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    objects_per_bucket = 10_000
    catalog, trace = build_workload(args.seed)
    log(f"catalog {catalog.n_objects} objects in {catalog.n_buckets} "
        f"buckets, trace {len(trace)} queries / "
        f"{sum(q.n_objects for q in trace)} probes, "
        f"set-up {time.perf_counter() - t0:.1f} s")
    reports = run_smoke(catalog, trace, objects_per_bucket, args.seed, log=log)
    if not all(r["ok"] for r in reports):
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
