"""End-to-end cross-match: real join compute through the LifeRaft engine.

Unlike quickstart.py (pure scheduling simulation), this drives the full
Fig. 3 architecture: Query Pre-Processor -> Workload Manager -> LifeRaft
Scheduler -> Join Evaluator (the cross-match kernel) -> Bucket Cache, and
reports both scheduling metrics and actual match results.

    PYTHONPATH=src python examples/crossmatch_skyquery.py [--pallas]
"""
import argparse

import jax
import numpy as np

from repro.core import (
    CostModel,
    HybridCostModel,
    HybridPlanner,
    LifeRaftScheduler,
)
from repro.compile_cache import enable_compile_cache
from repro.crossmatch import CrossMatchEngine, TraceConfig, make_catalog, make_trace


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pallas", action="store_true",
                    help="run the Pallas kernel instead of jnp (compiled on a "
                         "TPU, interpreted on the CPU)")
    ap.add_argument("--queries", type=int, default=60)
    ap.add_argument("--alpha", type=float, default=0.25)
    args = ap.parse_args()
    enable_compile_cache()

    cat = make_catalog(n_objects=40_000, objects_per_bucket=400, htm_level=8, seed=3)
    trace = make_trace(
        cat, TraceConfig(n_queries=args.queries, arrival_rate=1.0,
                         objects_median=200, seed=4),
    )
    cost = CostModel(T_b=1.2, T_m=0.13e-3)
    hybrid = HybridPlanner(
        HybridCostModel(T_b=1.2, T_m=0.13e-3, T_probe=4.13e-3),
        objects_per_bucket=400,
    )
    engine = CrossMatchEngine(
        cat,
        scheduler=LifeRaftScheduler(cost, alpha=args.alpha),
        cost_model=cost,
        cache_capacity=20,
        match_radius_rad=5e-3,
        hybrid=hybrid,
        use_pallas=args.pallas,
    )
    path = f"pallas on {jax.default_backend()}" if args.pallas else "jnp"
    print(f"running {len(trace)} cross-match queries ({path} join path)...")
    results = engine.run(trace)
    n_matches = sum(len(r.probe_idx) for groups in results.values() for r in groups)
    s = engine.summary()
    print(f"  queries completed : {s['n_queries']}")
    print(f"  bucket batches    : {s['n_batches']}")
    print(f"  matched objects   : {n_matches}")
    print(f"  mean response     : {s['mean_response']:.1f}s (simulated)")
    print(f"  cache hit rate    : {s['cache_hit_rate']:.2f}")
    # probabilistic-join sanity: matched pairs really are within the radius
    dots = [
        float(r.best_dot.min())
        for groups in results.values()
        for r in groups
        if len(r.best_dot)
    ]
    if dots:
        print(f"  min matched cos   : {min(dots):.6f} "
              f"(threshold {np.cos(5e-3):.6f})")


if __name__ == "__main__":
    main()
