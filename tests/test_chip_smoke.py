"""``chip_smoke.py`` on the CPU: its phases at a tiny size (Pallas
interpreted) against the float64 reference, the reference check itself,
and its refusal to run anywhere but on a TPU."""
from __future__ import annotations

import dataclasses
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys
import tempfile

import jax
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
OBJECTS_PER_BUCKET = 2_000
SEED = 5


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cs():
    return _load_chip_smoke()


@pytest.fixture(scope="module")
def workload(cs):
    """100 buckets of 2,000 objects: dense enough that the 1e-3 rad
    radius matches a few percent of the probes."""
    return cs.build_workload(
        SEED, n_objects=200_000, objects_per_bucket=OBJECTS_PER_BUCKET,
        htm_level=8, n_queries=12, objects_median=80,
    )


def test_phases_agree_with_f64_reference(cs, workload):
    assert jax.default_backend() == "cpu"
    catalog, trace = workload
    reports = cs.run_smoke(
        catalog, trace, OBJECTS_PER_BUCKET, SEED, n_kernel_queries=6,
        log=lambda msg: None,
    )
    assert [r["phase"] for r in reports] == [name for name, _ in cs.PHASES]
    for r in reports:
        assert r["ok"], r
        assert r["disagreements"] == 0, r
        assert r["completed"] == r["queries"], r
        assert r["mosaic"] is False  # interpret mode holds no Mosaic kernel
        assert r["matched"] > 0, r
        assert r["max_dot_err"] <= cs.DELTA
    assert reports[0]["queries"] == len(trace)
    assert reports[1]["queries"] == reports[2]["queries"] == 6


def test_reference_check_counts_wrong_answers(cs, workload):
    """Each kind of wrong routed result is counted as a disagreement."""
    catalog, trace = workload
    # Per-query radii up to 4e-3 rad: most matches then lie well inside
    # the radius, where the matched status is firm.
    queries = cs.with_radii(trace[:4], SEED)
    ref = cs.reference_join(
        catalog, OBJECTS_PER_BUCKET, queries,
        lambda q: np.cos(q.meta["radius"]),
    )
    results = _serve(cs, catalog, queries)
    clean = cs.check_results(catalog, OBJECTS_PER_BUCKET, results, ref)
    assert clean["disagreements"] == 0 and clean["matched"] > 0

    recs = [(qid, r) for qid, rs in results.items() for r in rs]
    qid, rec = recs[0]
    bumped = dataclasses.replace(rec, best_dot=rec.best_dot + 10 * cs.DELTA)
    wrong = {**results, qid: [bumped] + results[qid][1:]}
    bad = cs.check_results(catalog, OBJECTS_PER_BUCKET, wrong, ref)
    assert bad["by_check"]["best_dot"] >= 1

    doubled = {**results, qid: results[qid] + [rec]}
    bad = cs.check_results(catalog, OBJECTS_PER_BUCKET, doubled, ref)
    assert bad["by_check"]["unknown_or_duplicate"] >= 1

    firm_matched = ref.firm & ref.matched
    dropped = {q: [] for q in results}
    bad = cs.check_results(catalog, OBJECTS_PER_BUCKET, dropped, ref)
    assert bad["by_check"]["status"] == int(firm_matched.sum()) > 0


def _serve(cs, catalog, queries):
    """The default engine's routed results for ``queries``."""
    with tempfile.TemporaryDirectory() as jd:
        results, completed, _, _ = cs.run_phase(catalog, queries, {}, jd)
    assert completed == {q.query_id for q in queries}
    return results


def test_main_refuses_cpu(cs, capsys):
    assert jax.default_backend() == "cpu"
    assert cs.main([]) != 0
    captured = capsys.readouterr()
    assert '"ok": true' not in captured.out
    assert "needs a TPU" in captured.err


def test_lone_script_fails(tmp_path):
    """Copied into a directory that holds nothing else of the repo, the
    script exits non-zero and prints no result."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
