"""Query-plan benchmark: the QuerySpec v2 surface across spec kinds,
metrics and backends.

Measures, on one resident cloud:

* ``KnnSpec`` vs ``RangeSpec`` vs ``HybridSpec`` latency on the trueknn
  and brute backends (native grid paths vs dense kernel paths),
* l2 vs l1 on the brute backend (MXU matmul-identity path vs VPU |diff|
  tile path) and cosine via the trueknn backend's transformed companion
  cloud (the monotone-L2-reduction plan),
* which plan answered (``result.timings["plan"]``) — so regressions from
  "native" to a generic fallback show up in the trajectory, not just as a
  silent slowdown.

Emits CSV rows via the harness contract and returns a summary dict that
benchmarks/run.py serializes to BENCH_query_plans.json (uploaded as a CI
artifact next to BENCH_index.json).
"""

from __future__ import annotations

import numpy as np

from repro.api import (
    HybridSpec,
    KnnSpec,
    RangeSpec,
    build_index,
    dropped_counts,
    warm_default_radius,
)
from repro.core import make_dataset

from .common import emit, timed


def _bench_spec(index, queries, spec, metric="l2"):
    res, secs = timed(lambda: index.query(queries, spec, metric=metric))
    plan = res.timings.get("plan", "native")  # legacy tag (back-compat)
    route = index.prepare(spec, metric=metric).explain()["route"]
    return res, secs, plan, route


def main(n=16_000, n_queries=512, k=8) -> dict:
    pts = make_dataset("kitti", n, seed=0)
    rng = np.random.default_rng(1)
    qs = pts[rng.integers(0, n, n_queries)] + rng.normal(
        scale=0.5, size=(n_queries, pts.shape[1])
    ).astype(np.float32)

    summary: dict = {"n": n, "n_queries": n_queries, "k": k, "cells": {}}

    def record(name, res, secs, plan, route, derived=""):
        us = secs * 1e6 / n_queries
        summary["cells"][name] = {
            "us_per_query": round(us, 2),
            "plan": plan,
            "route": route,
            "n_tests": int(getattr(res, "n_tests", 0)),
        }
        emit(f"query_plans/{name}", us, f"route={route} {derived}".strip())

    # resident indexes; knn warms the trueknn grids so spec comparisons are
    # steady-state (the serving regime the API exists for)
    tk = build_index(pts, backend="trueknn")
    br = build_index(pts, backend="brute")
    warm = tk.query(qs, KnnSpec(k))
    # median *finite* k-th-NN distance (inf rows from unfilled queries must
    # not poison the default radius); falls back to the sampled radius
    radius = warm_default_radius(warm.dists, tk)

    # -- spec kinds on the grid path ---------------------------------------
    res, secs, plan, route = _bench_spec(tk, qs, KnnSpec(k))
    record("trueknn/knn/l2", res, secs, plan, route, f"rounds={res.n_rounds}")
    res, secs, plan, route = _bench_spec(tk, qs, RangeSpec(radius))
    record("trueknn/range/l2", res, secs, plan, route,
           f"nnz={len(res.idxs)} rows_max={int(res.counts.max())}")
    res, secs, plan, route = _bench_spec(tk, qs, HybridSpec(k, radius))
    partial, empty = dropped_counts(res.dists)  # queries, not inf cells
    record("trueknn/hybrid/l2", res, secs, plan, route,
           f"dropped_partial={partial} dropped_empty={empty}")

    # -- spec kinds on the dense kernel path -------------------------------
    res, secs, plan, route = _bench_spec(br, qs, KnnSpec(k))
    record("brute/knn/l2", res, secs, plan, route)
    res, secs, plan, route = _bench_spec(br, qs, RangeSpec(radius))
    record("brute/range/l2", res, secs, plan, route, f"nnz={len(res.idxs)}")
    res, secs, plan, route = _bench_spec(br, qs, HybridSpec(k, radius))
    record("brute/hybrid/l2", res, secs, plan, route)

    # -- metric dispatch ---------------------------------------------------
    res, secs, plan, route = _bench_spec(br, qs, KnnSpec(k), metric="l1")
    record("brute/knn/l1", res, secs, plan, route)
    res, secs, plan, route = _bench_spec(br, qs, KnnSpec(k), metric="linf")
    record("brute/knn/linf", res, secs, plan, route)
    res, secs, plan, route = _bench_spec(tk, qs, KnnSpec(k), metric="cosine")
    record("trueknn/knn/cosine", res, secs, plan, route)
    res, secs, plan, route = _bench_spec(tk, qs, KnnSpec(k), metric="l1")
    record("trueknn/knn/l1", res, secs, plan, route)

    l2 = summary["cells"]["brute/knn/l2"]["us_per_query"]
    l1 = summary["cells"]["brute/knn/l1"]["us_per_query"]
    summary["l1_over_l2_brute"] = round(l1 / max(l2, 1e-9), 2)
    summary["range_radius"] = radius
    emit(
        "query_plans/summary",
        summary["cells"]["trueknn/knn/l2"]["us_per_query"],
        f"l1_over_l2_brute={summary['l1_over_l2_brute']}x "
        f"cosine_route={summary['cells']['trueknn/knn/cosine']['route']}",
    )
    return summary


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import json

    print(json.dumps(main(), indent=2, default=str))
