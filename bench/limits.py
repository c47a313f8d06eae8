#!/usr/bin/env python3
"""Readings the check's limits are set from, in one process on one chip.

    python3 bench/limits.py --workload <cell> --seconds 10 \
        --seeds 11 12 ... --control 3 [--out bench/out/limits.json]

Each seed is a run of the cell at its own size and load (a shorter
window), checked as the benchmark checks it.  For the first ``--control``
seeds the control is checked too: the reference join computed as
``Precision.HIGH`` (three bf16 passes) in the program's place, over the
same sampled queries.  A limit lies above the largest sound reading and
below the smallest control reading (PERF.md gives both).  The benchmark's
runs do not call this.
"""
import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import harness, reference
    from repro.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("limits: needs a TPU", file=sys.stderr)
        return 1
    enable_compile_cache()
    rows = []
    for i, seed in enumerate(args.seeds):
        cell = harness.load_cell(args.workload)
        run, rep = harness.run_cell(cell, seed, args.seconds, workers=8,
                                    log=lambda m: print(f"  {m}", flush=True))
        row = {"seed": seed, "correct": rep["correct"],
               **{k: rep[k] for k in ("wrong", "dot_err", "unfinished", "units",
                                      "matched", "in_band", "checked_queries",
                                      "reference_s", "by_check")}}
        if i < args.control:
            t = time.perf_counter()
            ctl = reference.control_results(
                run.extra["catalog"], run.extra["layout"], run.extra["sample"],
                harness.pred_of(cell.config))
            crep = reference.check_results(run.extra["layout"], ctl, run.extra["reference"])
            row["control"] = {k: crep[k] for k in ("wrong", "dot_err", "matched", "by_check")}
            row["control"]["seconds"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)
        rows.append(row)
    if args.out:
        pathlib.Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
