#!/usr/bin/env python3
"""Chip benchmark of the LifeRaft cross-match service: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells, metrics and bounds are in ``BENCHMARK.json``; ``bench/harness.py``
says what a run does.  With ``--trace 0`` the result reports the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window and the benchmark's spans.  Any platform but
``tpu``, fewer chips than the cell asks for, or a device kind missing from
``bench/peaks.json`` ends the run with a non-zero exit and no result.

The last line of standard output is the result, one JSON object; the
numbers the check compared, each beside its limit, are the last lines of
standard error and the result's last key.  Everything else a run learned
goes to earlier lines and to ``bench/out/<cell>.<seed>.trace<0|1>.json``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
OUT = ROOT / "bench" / "out"


def _fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    try:
        from bench import harness, roofline, trace_reduce
        from repro.compile_cache import enable_compile_cache
    except ImportError as exc:
        return _fail(f"the program or the benchmark is missing here: {exc}")
    try:
        cell = harness.load_cell(args.workload)
    except (OSError, KeyError, harness.BenchError) as exc:
        return _fail(f"cannot load workload {args.workload!r}: {exc!r}")

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return _fail(f"needs a TPU, found {dev.platform!r}")
    if len(devices) < cell.chips:
        return _fail(f"{cell.name} needs {cell.chips} chips, found {len(devices)}")
    try:
        peak = roofline.load_peaks(dev.device_kind)
    except KeyError as exc:
        return _fail(str(exc))

    def log(msg):
        print(f"bench: {msg}", flush=True)

    log(f"compile cache: {enable_compile_cache()}")
    seed = args.seed % (1 << 63)
    tag = f"{cell.name}.{args.seed}.trace{args.trace}"
    trace_dir = None
    if args.trace:
        trace_dir = OUT / "trace" / tag
        shutil.rmtree(trace_dir, ignore_errors=True)
    workers = min(8, os.cpu_count() or 1)
    run, report = harness.run_cell(
        cell, seed, args.seconds, t_start=T_START, trace_dir=trace_dir,
        workers=workers, log=log,
    )
    run.peak = peak
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
        "memory_peak_bytes": run.extra["memory_peak_bytes"],
    }
    result = {
        "correct": report["correct"],
        "attempted": run.extra["attempted"],
        "failed": run.extra["failed"],
    }
    if args.trace:
        t = time.perf_counter()
        run.trace = trace_reduce.reduce(
            trace_reduce.load_events(trace_reduce.find_xplane(trace_dir))
        )
        log(f"trace reduced in {time.perf_counter() - t:.1f} s: "
            f"{run.trace['n_gaps']} idle gaps, longest {run.trace['longest_gap_s']!r} s")
        device.update(busy_s=run.trace["busy_s"], window_s=run.trace["window_s"])
        kind = "per_layer"
    else:
        kind = "end_to_end"
    result["metrics"] = harness.read_metrics(
        run, harness.cell_metrics(cell.spec, cell.name, kind)
    )
    result["device"] = device
    if args.trace:
        result["breakdown"] = trace_reduce.breakdown(run.trace)
    result["checks"] = report["checks"]

    responses = sorted(run.responses)
    notes = {
        "responses": len(responses),
        "p50_response_s": responses[len(responses) // 2] if responses else None,
        "completed_in_window": run.completed,
        "rounds_in_window": len(run.rounds),
        "queue_depth_end": run.extra["queue_depth_end"],
        "late_max_s": run.extra["late_max_s"],
        "late_mean_s": run.extra["late_mean_s"],
        "clock_drift_s": run.extra["clock_drift_s"],
        "warm_shapes": run.extra["warm_shapes"],
        "counters": run.counters,
        "check": {k: v for k, v in report.items() if k not in ("checks", "correct")},
    }
    if run.trace is not None:
        notes["trace"] = {k: v for k, v in run.trace.items() if k not in ("ops",)}
        notes["trace"]["top_ops"] = trace_reduce.breakdown(run.trace, 25)["device_ops"]
    for k, v in notes.items():
        log(f"{k}: {json.dumps(v, default=str)}")
    OUT.mkdir(parents=True, exist_ok=True)
    (OUT / f"{tag}.json").write_text(
        json.dumps({"result": result, "notes": notes}, indent=1, default=str)
    )
    for name, c in report["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
