"""Plan-cache benchmark: prepared plans vs per-call planning on the
sharded fabric.

The ROADMAP fabric items this tracks:

* **Executable-cache hit rate** — a prepared plan pads query counts and
  per-shard visit-sets to canonical pow2 shapes, so repeated batches with
  *different* shard mixes reuse compiled executables.  The summary
  reports the plan's bucket hit rate across repeated mixed-shard batches
  (CI bar: >= 0.9) and proves repeated mixes add no new buckets (no
  re-jit).
* **Prepared vs unprepared latency** — ``index.query`` re-plans per call
  with legacy (exact-size) shapes, so every fresh shard mix compiles new
  child-engine shapes; a prepared plan amortizes both.  The summary
  reports the speedup after warmup (CI bar: >= 1.5x).
* **Cross-shard n_tests parity** — the fused warm-start seed plus
  shared-cut rounds keep sharded kNN work within 1.2x of the monolithic
  trueknn index (ROADMAP parity item; the answers stay bit-identical).

Emits CSV rows via the harness contract and returns a summary dict that
benchmarks/run.py serializes to BENCH_plan_cache.json.
"""

from __future__ import annotations

import time

import numpy as np

from repro.api import KnnSpec, RangeSpec, build_index, warm_default_radius
from repro.core import make_dataset

from .common import emit


def _fresh_mixes(pts, rng, n_mixes, n_queries):
    """Query batches biased to different cloud regions, so each batch
    visits a different shard subset with different visit-set sizes."""
    n = len(pts)
    mixes = []
    for _ in range(n_mixes):
        anchor = pts[rng.integers(0, n)]
        d = np.linalg.norm(pts - anchor, axis=1)
        near = np.argsort(d)[: max(n // 3, n_queries)]
        sel = rng.choice(near, size=n_queries, replace=True)
        mixes.append(
            (pts[sel] + rng.normal(scale=0.01, size=(n_queries, pts.shape[1])))
            .astype(np.float32)
        )
    return mixes


def main(n=20_000, k=8, n_queries=256, n_shards=8, n_mixes=6) -> dict:
    pts = make_dataset("porto", n, seed=0)
    rng = np.random.default_rng(1)

    mono = build_index(pts, backend="trueknn")
    shard = build_index(
        pts, backend="sharded", n_shards=n_shards, child_backend="trueknn"
    )
    warm_qs = _fresh_mixes(pts, rng, 1, n_queries)[0]
    warm = mono.query(warm_qs, KnnSpec(k))
    shard.query(warm_qs, KnnSpec(k))
    radius = warm_default_radius(warm.dists, mono)
    spec = RangeSpec(radius, max_neighbors=2 * k)

    # -- n_tests parity: sharded kNN work vs the monolith ------------------
    ratios = []
    for qs in _fresh_mixes(pts, rng, 3, n_queries):
        a = mono.query(qs, KnnSpec(k))
        b = shard.query(qs, KnnSpec(k))
        assert np.array_equal(a.dists, b.dists), "sharded/mono divergence"
        ratios.append(b.n_tests / max(a.n_tests, 1))
    parity = round(max(ratios), 3)  # worst mix: the gate must hold everywhere
    emit("plan_cache/knn_tests_parity", parity * 1e3,
         f"sharded_over_mono_n_tests={parity} (bar <= 1.2)")

    # -- unprepared: per-call planning, legacy shapes ----------------------
    # warmup on its own mixes, then measure on FRESH mixes: each new shard
    # mix produces new exact-size child shapes, so the engines recompile
    for qs in _fresh_mixes(pts, rng, 2, n_queries):
        shard.query(qs, spec)
    t0 = time.perf_counter()
    for qs in _fresh_mixes(pts, rng, n_mixes, n_queries):
        shard.query(qs, spec)
    t_unprepared = time.perf_counter() - t0

    # -- prepared: one plan, canonical shapes ------------------------------
    plan = shard.prepare(spec)
    # warmup: a few mixes populate the canonical pow2 shape buckets (the
    # compile pass a serving tier pays once at startup)
    for qs in _fresh_mixes(pts, rng, 4, n_queries):
        plan(qs)
    before = plan.cache_stats()
    t0 = time.perf_counter()
    measured = _fresh_mixes(pts, rng, n_mixes, n_queries)
    for qs in measured:
        plan(qs)
    t_prepared = time.perf_counter() - t0
    mid = plan.cache_stats()
    # repeat the SAME mixes: canonical shapes mean zero new buckets
    for qs in measured:
        plan(qs)
    after = plan.cache_stats()

    d_hits = after["hits"] - before["hits"]
    d_miss = after["misses"] - before["misses"]
    hit_rate = round(d_hits / max(d_hits + d_miss, 1), 4)
    no_rejit = bool(after["buckets"] == mid["buckets"])
    speedup = round(t_unprepared / max(t_prepared, 1e-9), 3)

    us = t_prepared * 1e6 / (n_mixes * n_queries)
    emit("plan_cache/prepared_range", us,
         f"speedup={speedup}x hit_rate={hit_rate} no_rejit={no_rejit}")
    emit("plan_cache/unprepared_range",
         t_unprepared * 1e6 / (n_mixes * n_queries),
         "per-call planning, legacy shapes")

    summary = {
        "n": n,
        "k": k,
        "n_queries": n_queries,
        "n_shards": n_shards,
        "n_mixes": n_mixes,
        "range_radius": radius,
        "knn_tests_parity": {
            "sharded_over_mono": parity,
            "all_ratios": [round(r, 3) for r in ratios],
        },
        "executable_cache": {
            "hit_rate": hit_rate,
            "hits": d_hits,
            "misses": d_miss,
            "buckets": after["buckets"],
            "no_rejit_on_repeats": no_rejit,
        },
        "latency": {
            "prepared_s": round(t_prepared, 4),
            "unprepared_s": round(t_unprepared, 4),
            "prepared_speedup": speedup,
        },
    }
    emit("plan_cache/summary", us,
         f"speedup={speedup}x hit_rate={hit_rate} parity={parity}")
    return summary


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import json

    print(json.dumps(main(), indent=2, default=str))
