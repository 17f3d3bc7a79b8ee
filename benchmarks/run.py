"""Benchmark aggregator — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (the harness contract).  The
kernel microbenchmark runs at the end; the roofline table is produced
separately by ``benchmarks.roofline`` from the dry-run artifacts (it needs
the 512-device XLA flag and its own process).
"""

from __future__ import annotations

import json
import time


def _section(title):
    print(f"# --- {title} ---", flush=True)


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from . import (
        bench_brute,
        bench_dataset_size,
        bench_fused_loop,
        bench_graph,
        bench_index_reuse,
        bench_k,
        bench_kernel,
        bench_mutation,
        bench_percentile,
        bench_placement,
        bench_plan_cache,
        bench_query_plans,
        bench_rounds,
        bench_serve,
        bench_shards,
        bench_start_radius,
        bench_work_counts,
    )

    t0 = time.time()
    _section("paper Fig3/T1: dataset size sweep")
    bench_dataset_size.main()
    _section("paper T2: work counts")
    bench_work_counts.main()
    _section("paper Fig4: vs brute force")
    bench_brute.main()
    _section("paper Fig5: impact of k")
    bench_k.main()
    _section("paper Fig6: round breakdown")
    bench_rounds.main()
    _section("paper Fig7: start radius")
    bench_start_radius.main()
    _section("paper Fig8/9+T3: 99th percentile / outliers")
    bench_percentile.main()
    _section("index reuse (build-once/query-many serving)")
    index_summary = bench_index_reuse.main()
    with open("BENCH_index.json", "w") as f:
        json.dump(index_summary, f, indent=2, default=str)
    print("# wrote BENCH_index.json", flush=True)
    _section("query plans (QuerySpec v2: knn/range/hybrid x metrics)")
    plans_summary = bench_query_plans.main()
    with open("BENCH_query_plans.json", "w") as f:
        json.dump(plans_summary, f, indent=2, default=str)
    print("# wrote BENCH_query_plans.json", flush=True)
    _section("serving (NeighborServer: open-loop load, microbatching, cache)")
    serve_summary = bench_serve.main()
    with open("BENCH_serve.json", "w") as f:
        json.dump(serve_summary, f, indent=2, default=str)
    print("# wrote BENCH_serve.json", flush=True)
    _section("sharded fabric (merge identity, shard pruning, latency)")
    shards_summary = bench_shards.main()
    with open("BENCH_shards.json", "w") as f:
        json.dump(shards_summary, f, indent=2, default=str)
    print("# wrote BENCH_shards.json", flush=True)
    _section("placement (device-parallel fabric: fused dispatch, identity)")
    placement_summary = bench_placement.main()
    with open("BENCH_placement.json", "w") as f:
        json.dump(placement_summary, f, indent=2, default=str)
    print("# wrote BENCH_placement.json", flush=True)
    _section("plan cache (prepared plans: executable reuse, n_tests parity)")
    plan_cache_summary = bench_plan_cache.main()
    with open("BENCH_plan_cache.json", "w") as f:
        json.dump(plan_cache_summary, f, indent=2, default=str)
    print("# wrote BENCH_plan_cache.json", flush=True)
    _section("fused round loop (one dispatch per search: identity, latency)")
    fused_summary = bench_fused_loop.main()
    with open("BENCH_fused.json", "w") as f:
        json.dump(fused_summary, f, indent=2, default=str)
    print("# wrote BENCH_fused.json", flush=True)
    _section("graph workloads (kNN graph / DBSCAN identity, self-batch locality)")
    graph_summary = bench_graph.main()
    with open("BENCH_graph.json", "w") as f:
        json.dump(graph_summary, f, indent=2, default=str)
    print("# wrote BENCH_graph.json", flush=True)
    _section("mutation (LSM composite: storm identity, sustained, delta tax)")
    mutation_summary = bench_mutation.main()
    with open("BENCH_mutation.json", "w") as f:
        json.dump(mutation_summary, f, indent=2, default=str)
    print("# wrote BENCH_mutation.json", flush=True)
    _section("kernel microbench")
    bench_kernel.main()
    print(f"# total {time.time()-t0:.1f}s", flush=True)


if __name__ == "__main__":
    main()
