"""The CasJobs batch queue (``casjobs-shared.backlog``) on the CPU.

Queries that each carry their own radius and magnitude cut, the traffic's
25 predicate classes, are served through ``ServiceDaemon`` by the engine
the benchmark builds for the cell, at a small size with the Pallas kernels
interpreted, and checked against the benchmark's float64 reference.  With
``shared_plan`` on (8 queries a call) and off (a round's queries in one
call) the engine must pass and give the same bits.  The cell itself must
load by name, give every block of its stream each class once, and reach
only the shared kernel core.
"""
from __future__ import annotations

import pathlib
import sys
import tempfile

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness, reference  # noqa: E402
from bench.gen.catalog import build_catalog, normalize  # noqa: E402
from bench.gen.htm import htm_ids  # noqa: E402
from bench.gen.trace import block_shapes, make_stream  # noqa: E402
from repro.core.workload import Query  # noqa: E402
from repro.serving import CrossMatchHost, ServiceDaemon  # noqa: E402

CELL = "casjobs-shared.backlog"
SEED = 4_000_000_007
N_QUERIES = 50  # two of each of the 25 classes
PROBES = 12
_SERVED: dict = {}


def _small_config(shared_plan: bool) -> dict:
    """The cell's configuration cut to 8 buckets of 512 objects (still
    4,000 bytes each); ``fuse_k`` 4, ``share_width`` 8 as committed."""
    cfg = dict(harness.load_cell(CELL).config)
    cfg.update(n_buckets=8, objects_per_bucket=512, bucket_bytes=512 * 4000,
               htm_level=6, shared_plan=shared_plan)
    return cfg


def _queries(catalog, layout, preds) -> list[Query]:
    """Query i takes class ``i % 25``: probes are objects of one bucket
    moved by ~2.5e-3 rad, so each radius from 1e-3 to 4e-3 rad finds some
    of them and misses others."""
    rng = np.random.default_rng([SEED, 16])
    out = []
    for i in range(N_QUERIES):
        rows = rng.choice(layout.rows(int(rng.integers(layout.n_buckets))),
                          size=PROBES, replace=False)
        pos = normalize(catalog.positions[rows]
                        + rng.normal(scale=2e-3, size=(PROBES, 3)))
        keys = htm_ids(pos, catalog.level)
        radius, cut = preds[i % len(preds)]
        out.append(Query(
            query_id=i, arrival_time=0.0, keys_lo=keys, keys_hi=keys,
            payload={"positions": pos},
            meta={"radius": float(radius), "mag_cut": float(cut)},
        ))
    return out


def _serve(shared_plan: bool):
    """(config, catalog, layout, queries, engine) after serving every
    query through the daemon."""
    if shared_plan not in _SERVED:
        cfg = _small_config(shared_plan)
        catalog = build_catalog(cfg, SEED)
        layout = reference.Layout(catalog.htm, cfg["objects_per_bucket"])
        preds = block_shapes(harness.load_cell(CELL).mix)["pred"]
        queries = _queries(catalog, layout, preds)
        engine = harness.make_engine(cfg, catalog)
        with tempfile.TemporaryDirectory() as journal:
            daemon = ServiceDaemon(CrossMatchHost(engine), journal)
            for q in queries:
                daemon.submit(q)
            daemon.pump()
            daemon.close()
        _SERVED[shared_plan] = (cfg, catalog, layout, queries, engine)
    return _SERVED[shared_plan]


def _flat(results, qid):
    """One query's routed matches in (probe, object) order."""
    recs = results[qid]
    if not recs:
        return [np.zeros(0, np.int64)] * 3 + [np.zeros(0, np.int32)]
    probe = np.concatenate([r.probe_idx for r in recs])
    obj = np.concatenate([r.match_obj for r in recs])
    dot = np.concatenate([r.best_dot for r in recs]).astype(np.float32)
    cnt = np.concatenate([r.n_candidates for r in recs])
    order = np.lexsort((obj, probe))
    return probe[order], obj[order], cnt[order], dot[order].view(np.int32)


@pytest.mark.parametrize("shared_plan", [True, False], ids=["shared", "per_predicate"])
def test_served_predicates_match_the_reference(shared_plan):
    cfg, catalog, layout, queries, engine = _serve(shared_plan)
    assert len({(q.meta["radius"], q.meta["mag_cut"]) for q in queries}) == 25
    assert {q.query_id for q in queries} <= set(engine.wm.completed)
    loop = engine.loop
    assert loop.shared_calls == loop.device_dispatches > 0
    assert loop.shared_classes > loop.shared_calls
    if not shared_plan:
        assert loop.device_dispatches == loop.dispatches
    ref = reference.reference_join(catalog, layout, queries, harness.pred_of(cfg))
    rep = reference.check_results(layout, engine.results, ref)
    assert rep["wrong"] == 0, rep
    assert rep["dot_err"] <= cfg["dot_err_limit"], rep
    assert 0 < rep["matched"] < rep["units"], rep
    # Every radius finds matches, and every cut below the catalog's faint
    # end drops some nearest objects that lie within the radius.
    mix = harness.load_cell(CELL).mix
    hit = {q.query_id for q in queries if engine.results[q.query_id]}
    assert {queries[i].meta["radius"] for i in hit} == set(mix["radii_rad"])
    qid = ref.key >> 40
    thr = np.array([harness.pred_of(cfg)(q)[0] for q in queries], np.float32)
    dropped = (ref.best_dot >= thr[qid]) & ~ref.matched
    cuts = {queries[i].meta["mag_cut"] for i in np.unique(qid[dropped])}
    assert cuts == set(mix["mag_cuts"]) - {cfg["mag_hi"]}
    # The other path gives the same answers, bit for bit.
    other = _serve(not shared_plan)[4]
    for q in queries:
        for a, b in zip(_flat(engine.results, q.query_id),
                        _flat(other.results, q.query_id)):
            np.testing.assert_array_equal(a, b)


def test_cell_loads_with_every_class_once_a_block_on_the_shared_core():
    cell = harness.load_cell(CELL)
    assert cell.config["shared_plan"] and cell.config["share_width"] == 8
    mix = cell.mix
    classes = {(r, m) for r in mix["radii_rad"] for m in mix["mag_cuts"]}
    assert len(classes) == mix["block"] == 25
    stream = make_stream(mix, cell.config["htm_level"], SEED)
    b = mix["block"]
    assert len(stream.queries) == mix["n_queries"] and mix["n_queries"] % b == 0
    for j in range(0, len(stream.queries), b):
        block = [(q.meta["radius"], q.meta["mag_cut"])
                 for q in stream.queries[j : j + b]]
        assert sorted(block) == sorted(classes)
    cfg = dict(cell.config, n_buckets=8, objects_per_bucket=512,
               bucket_bytes=512 * 4000)
    catalog = build_catalog(cfg, SEED)
    layout = reference.Layout(catalog.htm, cfg["objects_per_bucket"])
    shapes = harness.reachable_shapes(cfg, mix, layout, stream.queries)
    assert list(shapes["cores"]) == ["shared"] and shapes["cores"]["shared"]
