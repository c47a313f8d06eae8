"""Benchmark harness — one section per paper table/figure + system benches.

Prints ``name,us_per_call,derived`` CSV lines (one per benchmark) between
human-readable sections.  Roofline tables come from ``launch/dryrun.py``
artifacts and are summarized by ``roofline_table.py``.
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    t0 = time.time()
    from . import (  # noqa: E402
        bench_adaptive,
        bench_obs,
        bench_prefetch,
        bench_scheduler,
        bench_shard,
        fig2_hybrid_join,
        fig5_bucket_reuse,
        fig6_workload_cdf,
        fig7_schedulers,
        fig8_tradeoff,
        serving_bench,
        kernel_bench,
        ft_bench,
        roofline_table,
    )

    sections = [
        ("Fig.2 hybrid join (scan vs index break-even)", fig2_hybrid_join.main),
        ("Fig.5 bucket reuse (top-10 coverage)", fig5_bucket_reuse.main),
        ("Fig.6 cumulative workload CDF", fig6_workload_cdf.main),
        ("Fig.7 schedulers (throughput / response / cache)", fig7_schedulers.main),
        ("Fig.8 saturation trade-off + adaptive alpha", fig8_tradeoff.main),
        ("Scheduler hot path: incremental vs naive + compile counts", bench_scheduler.main),
        ("Adaptive control plane: closed loop vs best static alpha", bench_adaptive.main),
        ("Prefetch: scan-horizon staging vs reactive LRU", bench_prefetch.main),
        ("Sharding: multi-shard tier + work stealing vs one loop", bench_shard.main),
        ("Observability: obs-on/off overhead + snapshot/Perfetto artifacts", bench_obs.main),
        ("Serving: multi-tenant LifeRaft engine", serving_bench.main),
        ("Kernels: micro-benchmarks", kernel_bench.main),
        ("Fault tolerance: goodput under failures", ft_bench.main),
        ("Roofline: dry-run artifact summary", roofline_table.main),
    ]
    failures = 0
    for title, fn in sections:
        print(f"\n=== {title} ===")
        try:
            fn()
        except Exception as e:  # keep the harness running
            failures += 1
            print(f"  BENCH-ERROR {title}: {type(e).__name__}: {e}")
    print(f"\n[benchmarks] done in {time.time() - t0:.0f}s, failures={failures}")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
