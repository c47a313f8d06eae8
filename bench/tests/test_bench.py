"""The benchmark on the CPU: its spec and files, the harness core at a tiny
size (Pallas interpreted), the reference check, the control and the
faults it has to catch, the roofline's yardstick, the trace reduction and
the refusals.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import dataclasses
import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, reference, roofline, trace_reduce  # noqa: E402

SEED = 4_000_000_007  # larger than 32 signed bits hold
DATA = pathlib.Path(__file__).resolve().parent / "data"


CELL = "skyquery-fused.backlog"

# The cell as committed, and an open loop with per-query radii and
# magnitude cuts on the shared-plan kernel: the other paths of the harness
# that a later cell can name by its data files alone.
VARIANTS = ["closed", "open_shared"]


def tiny(variant: str = "closed") -> harness.Cell:
    """The cell cut to 8 buckets of 512 objects (still 4,000 bytes each),
    with radii wide enough that a few percent of the probes match."""
    cell = harness.load_cell(CELL)
    cell.config.update(n_buckets=8, objects_per_bucket=512, bucket_bytes=512 * 4000,
                       htm_level=6, fuse_k=2, share_width=4, match_radius_rad=0.03)
    cell.mix.update(n_queries=200, objects_median=12, warmup_s=0.5, drain_s=30,
                    check_queries=12, outstanding=6)
    if variant == "open_shared":
        cell.config["shared_plan"] = True
        cell.mix.update(loop="open", rate_per_s=20.0, radii_rad=[0.01, 0.02, 0.03, 0.05],
                        mag_cuts=[22.0, 24.0])
    return cell


def serve(cell, seconds=1.5):
    return harness.run_cell(cell, SEED, seconds, workers=1)


# ------------------------------------------------------------------ spec
def test_every_cell_config_mix_and_metric_loads_by_name():
    spec = harness.load_spec()
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["bench"]
    configs = {c["name"]: c for c in spec["configs"]}
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert cfg[key] != cfg["published"][key]
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert w["config"] in configs and cell.config["name"] == w["config"]
        assert cell.mix["loop"] in ("open", "closed")
        e2e = harness.cell_metrics(spec, w["name"], "end_to_end")
        names = {m["name"] for m in e2e}
        assert "setup_s" in names and len(names) >= 2
        per_layer = harness.cell_metrics(spec, w["name"], "per_layer")
        assert per_layer
        assert all(m["moves"] in names for m in per_layer)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
    layers = {}
    for m in spec["per_layer"]:
        layers.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


# ------------------------------------------------------------------ serving
@pytest.mark.parametrize("variant", VARIANTS)
def test_harness_serves_a_tiny_cell_and_passes_its_check(variant):
    assert jax.default_backend() == "cpu"
    cell = tiny(variant)
    run, report = serve(cell)
    assert report["correct"], report
    assert report["matched"] > 0 and report["checked_queries"] > 0
    assert run.extra["attempted"] > 0 and run.extra["failed"] == 0
    assert list(report["checks"]) == ["wrong", "dot_err", "unfinished"]
    e2e = harness.read_metrics(run, harness.cell_metrics(cell.spec, CELL, "end_to_end"))
    assert set(e2e) == {m["name"] for m in harness.cell_metrics(cell.spec, CELL, "end_to_end")}
    assert all(v["value"] > 0 for v in e2e.values())
    layer = harness.read_metrics(run, harness.cell_metrics(cell.spec, CELL, "per_layer"))
    # Without a trace the device's readers find nothing and stay silent.
    assert not any(k.startswith(("device_idle_share", "xmatch_roofline")) for k in layer)
    assert layer


def test_same_seed_same_stream_and_catalog():
    cell = tiny("open_shared")
    from bench.gen.catalog import build_catalog, row_bytes
    from bench.gen.trace import make_stream

    a, b = make_stream(cell.mix, 6, SEED), make_stream(cell.mix, 6, SEED)
    assert np.array_equal(a.gaps, b.gaps)
    for qa, qb in zip(a.queries, b.queries):
        assert np.array_equal(qa.payload["positions"], qb.payload["positions"])
        assert np.array_equal(qa.keys_lo, qb.keys_lo) and qa.meta == qb.meta
    cat1 = build_catalog(cell.config, SEED)
    cat2 = build_catalog(cell.config, SEED)
    assert np.array_equal(cat1.positions, cat2.positions)
    assert np.array_equal(cat1.htm, cat2.htm)
    # A bucket read moves every byte of its objects at the stated width.
    assert row_bytes(cell.config) == 4000
    bucket = cat1.store.read(0)
    n = len(bucket["positions"])
    assert sum(v.nbytes for v in bucket.values()) == n * 4000


def test_every_seed_gets_the_same_work():
    """Each block holds the same multiset of sizes, kinds, predicates and
    gaps whatever the seed; the seed only orders and places them."""
    cell = tiny("open_shared")
    b = cell.mix["block"]

    def blocks(seed):
        s = harness.make_stream(cell.mix, 6, seed)
        out = []
        for j in range(0, len(s.queries), b):
            qs = s.queries[j : j + b]
            out.append((
                sorted(q.n_objects for q in qs),
                sum(q.meta["fullsky"] for q in qs),
                sorted((q.meta["radius"], q.meta["mag_cut"]) for q in qs),
                sorted(np.round(s.gaps[j : j + b], 12)),
            ))
        return out

    assert blocks(1) == blocks(SEED)
    s1, s2 = harness.make_stream(cell.mix, 6, 1), harness.make_stream(cell.mix, 6, SEED)
    assert [q.n_objects for q in s1.queries] != [q.n_objects for q in s2.queries]


# ------------------------------------------------------------------ check
@pytest.fixture(scope="module")
def checked():
    """A served tiny run: its routed results for the sample and the
    reference they were checked against."""
    cell = tiny()
    cell.config["use_pallas"] = False
    run, report = serve(cell)
    assert report["correct"], report
    layout = run.extra["layout"]
    ref = reference.reference_join(run.extra["catalog"], layout, run.extra["sample"],
                                   harness.pred_of(cell.config))
    return cell, run, layout, ref


def test_check_counts_each_kind_of_wrong_answer(checked):
    cell, run, layout, ref = checked
    results = run.extra["results"]
    clean = reference.check_results(layout, results, ref)
    assert clean["wrong"] == 0 and clean["matched"] > 0

    qid, rec = next((q, r) for q, rs in results.items() for r in rs)
    def swap(new):
        return {**results, qid: [new] + results[qid][1:]}

    bad = reference.check_results(layout, swap(dataclasses.replace(
        rec, best_dot=rec.best_dot + 10 * reference.DELTA)), ref)
    assert bad["dot_err"] > reference.DELTA
    bad = reference.check_results(layout, {**results, qid: results[qid] + [rec]}, ref)
    assert bad["by_check"]["unknown_or_duplicate"] >= 1
    bad = reference.check_results(layout, swap(dataclasses.replace(
        rec, n_candidates=rec.n_candidates + 1)), ref)
    assert bad["by_check"]["n_cand"] >= 1
    rows = layout.rows(int(layout.bucket_of_row[rec.match_obj[0]]))
    other = np.where(rec.match_obj == rows[0], rows[1], rows[0])
    bad = reference.check_results(layout, swap(dataclasses.replace(rec, match_obj=other)), ref)
    assert bad["by_check"]["best_idx"] >= 1
    firm_matched = ref.firm & ref.matched
    bad = reference.check_results(layout, {q: [] for q in results}, ref)
    assert bad["by_check"]["status"] == int(firm_matched.sum()) > 0
    moved = dataclasses.replace(rec, query_id=max(results) + 10_000)
    bad = reference.check_results(layout, swap(moved), ref)
    assert bad["by_check"]["unknown_or_duplicate"] >= 1


def test_lower_precision_control_is_not_correct(checked):
    """The reference computed as ``Precision.HIGH`` (three bf16 passes) in
    the program's place fails the check the served answers pass."""
    cell, run, layout, ref = checked
    ctl = reference.control_results(run.extra["catalog"], layout, run.extra["sample"],
                                    harness.pred_of(cell.config))
    rep = reference.check_results(layout, ctl, ref)
    assert rep["wrong"] > 0 or rep["dot_err"] > cell.config["dot_err_limit"], rep
    assert rep["dot_err"] > cell.config["dot_err_limit"]


def _fault(monkeypatch, kind):
    """Break the timed path underneath the harness."""
    from repro.crossmatch.engine import CrossMatchEngine
    from repro.kernels.crossmatch import ops

    if kind == "state_unchanged":
        monkeypatch.setattr(CrossMatchEngine, "_route", lambda self, *a, **k: None)
        return
    for name in ("crossmatch", "crossmatch_fused", "crossmatch_shared"):
        real = getattr(ops, name)

        def broken(bucket, probes, *a, _real=real, **k):
            idx, dot, cnt = (np.asarray(x).copy() for x in _real(bucket, probes, *a, **k))
            if kind == "half_batch":
                half = len(probes) // 2
                cnt[half:] = 0
                dot[half:] = -1.0
            else:  # an answer altered where it is produced
                idx = np.where(cnt > 0, (idx + 1) % len(bucket), idx)
            return idx, dot, cnt

        monkeypatch.setattr(ops, name, broken)


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch", "answer_altered"])
@pytest.mark.parametrize("variant", VARIANTS)
def test_a_broken_timed_path_is_not_correct(variant, kind, monkeypatch):
    """The exchange between chips is not a fault this cell can have: it
    runs on one chip."""
    cell = tiny(variant)
    cell.config["use_pallas"] = False
    _fault(monkeypatch, kind)
    _, report = serve(cell, seconds=1.0)
    assert not report["correct"], report


# ------------------------------------------------------------------ yardstick
def test_pair_work_counts_same_segment_pairs_only():
    # one bucket of 10,000 objects against 300 probes
    f, b = roofline.pair_work([(300, 10_000)])
    assert f == 6 * 300 * 10_000
    assert b == 300 * (12 + 12) + 10_000 * 12
    # four segments with unequal probe counts: 4*(10k) objects, 1+2+3+4 = 10 probes x 10k
    segs = [(1, 10_000), (2, 10_000), (3, 10_000), (4, 10_000)]
    f, b = roofline.pair_work(segs)
    assert f == 6 * 10 * 10_000  # not 6 * 10 * 40,000
    assert b == 10 * 24 + 40_000 * 12
    # shared plan: the same pairs, plus a float32 threshold per probe
    f2, b2 = roofline.pair_work(segs, per_probe_threshold=True)
    assert f2 == f and b2 == b + 10 * 4
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert roofline.least_seconds(1000, 50, peak) == (10.0, "compute")
    assert roofline.least_seconds(1000, 500, peak) == (50.0, "memory")


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.load_peaks("TPU v99 imaginary")
    assert roofline.load_peaks("TPU v5 lite")["flops_per_s"] == 197e12


# ------------------------------------------------------------------ trace
def _ev(plane, line, name, start, dur):
    return trace_reduce.Event(plane, line, name, float(start), float(dur))


def test_trace_reduce_on_hand_built_events():
    dev, host = "/device:TPU:0", "/host:CPU"
    events = [
        _ev(host, "python", "bench.window", 100, 1000),
        _ev(host, "python", "bench.step", 100, 500),
        _ev(host, "python", "backend_compile", 150, 100),
        _ev(host, "python", "bench.submit", 600, 400),
        _ev(dev, "XLA Ops", "fusion", 50, 100),  # clipped to [100, 150)
        _ev(dev, "XLA Ops", "kernel", 300, 100),
        _ev(dev, "XLA Ops", "copy", 350, 100),  # overlaps kernel: union [300, 450)
        _ev(dev, "XLA Modules", "jit__crossmatch_fused_jit", 300, 150),
        _ev(dev, "XLA Ops", "late", 1050, 200),  # clipped to [1050, 1100)
    ]
    red = trace_reduce.reduce(events)
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx((50 + 150 + 50) * 1e-9)
    assert red["ops"] == pytest.approx({"fusion": 50e-9, "kernel": 100e-9,
                                        "copy": 100e-9, "late": 50e-9})
    assert red["modules"] == pytest.approx({"jit__crossmatch_fused_jit": 150e-9})
    # gaps: [150, 300) mid 225 in step > compile; [450, 1050) mid 750 in submit
    assert red["idle_by_activity"] == pytest.approx({
        "bench.step > backend_compile": 150e-9, "bench.submit": 600e-9})
    assert red["n_gaps"] == 2
    bd = trace_reduce.breakdown(red, top=2)
    assert bd["device_ops"] == [["kernel", pytest.approx(100e-9)], ["copy", pytest.approx(100e-9)]]
    assert bd["idle_gaps"][0][0] == "bench.submit"


def test_trace_reduce_on_a_recorded_chip_trace():
    """A one-second window of ``skyquery-fused.backlog`` traced on one TPU
    v5e chip (seed 303).  Busy time is checked against a 10 ns grid painted
    from the raw op events; the other values were read off the trace once
    and are frozen here."""
    events = trace_reduce.load_events(DATA / "backlog_1s.xplane.pb")
    red = trace_reduce.reduce(events)
    assert red["devices"] == ["/device:TPU:0"]
    w = next(e for e in events if e.name == "bench.window")
    assert red["window_s"] == pytest.approx(w.dur_ns * 1e-9) == pytest.approx(1.095016234)
    ops = [e for e in events if e.plane == "/device:TPU:0" and e.line == "XLA Ops"]
    grid = np.zeros(int(w.dur_ns // 10) + 1, bool)
    for e in ops:
        a, b = max(e.start_ns, w.start_ns), min(e.end_ns, w.end_ns)
        if b > a:
            grid[int((a - w.start_ns) // 10):int(np.ceil((b - w.start_ns) / 10))] = True
    assert red["busy_s"] == pytest.approx(grid.sum() * 10e-9, abs=len(ops) * 20e-9)
    assert red["busy_s"] == pytest.approx(0.002577311)
    assert red["ops"]["crossmatch_fused_pallas.1"] == pytest.approx(0.002430679)
    assert red["modules"] == pytest.approx({
        "jit__crossmatch_fused_jit": 0.002571194,
        "jit_dynamic_slice": 1.0725e-05, "jit_minimum": 3.998e-06})
    idle = red["idle_by_activity"]
    assert sum(idle.values()) == pytest.approx(red["window_s"] - red["busy_s"])
    assert idle["bench.step > backend_compile_and_load"] == pytest.approx(0.782963144)
    assert idle["bench.submit"] == pytest.approx(0.286218852)
    assert red["n_gaps"] == 82
    assert trace_reduce.breakdown(red)["device_ops"][0][0] == "crossmatch_fused_pallas.1"


# ------------------------------------------------------------------ refusals
def _run_py(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL,
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_run_refuses_the_cpu():
    proc = _run_py(ROOT)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    proc = _run_py(tmp_path)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
