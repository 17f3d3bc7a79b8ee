"""Sharded-fabric benchmark: merge identity, shard pruning, latency.

Measures, on the clustered dataset (the paper family's heavy-tailed case,
where spatial partitioning should pay):

* **merge identity** — a ``sharded`` index over trueknn children must
  answer kNN / hybrid / range specs *exactly* like the monolithic trueknn
  index over the same cloud (``np.array_equal``, not allclose: the merge
  layer's whole contract is bit-identity).  The summary carries one flag
  per spec kind so CI can assert on them.
* **shard pruning** — the fraction of potential (query, shard) visits the
  radius-aware pruning skipped, per spec kind, read off the
  ``sharded/pruned=<m-of-n>`` plan accounting.  The acceptance bar for the
  clustered dataset at default k is >= 50% on kNN.
* **latency** — best-of-reps wall clock for the same batch on the
  monolithic vs the sharded index (plus the tail shape).  On a CPU host
  the fabric's per-shard dispatch overhead usually loses to one fused
  monolithic pass — the number is recorded honestly either way; the
  fabric's job at this stage is exactness + work reduction (``n_tests``,
  visits), which the summary also carries.

Emits CSV rows via the harness contract and returns a summary dict that
benchmarks/run.py serializes to BENCH_shards.json.
"""

from __future__ import annotations

import time

import numpy as np

from repro.api import (
    HybridSpec,
    KnnSpec,
    RangeSpec,
    build_index,
    warm_default_radius,
)
from repro.core import make_dataset

from .common import emit


def _prune_rate(res) -> float:
    v = res.timings["shard_visits"]
    p = res.timings["shard_potential"]
    return round(1.0 - v / p, 4) if p else 0.0


def _time_best(fn, reps: int) -> float:
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def main(n=20_000, k=8, n_queries=512, n_shards=8, reps=3,
         child_backend="trueknn") -> dict:
    pts = make_dataset("porto", n, seed=0)  # clustered: pruning's home turf
    rng = np.random.default_rng(1)
    qs = (
        pts[rng.integers(0, n, n_queries)]
        + rng.normal(scale=0.01, size=(n_queries, pts.shape[1]))
    ).astype(np.float32)

    mono = build_index(pts, backend=child_backend)
    shard = build_index(
        pts, backend="sharded", n_shards=n_shards,
        child_backend=child_backend,
    )
    # warm pass: sampling, grid builds, jit for both index shapes
    warm = mono.query(qs, KnnSpec(k))
    shard.query(qs, KnnSpec(k))
    radius = warm_default_radius(warm.dists, mono)

    specs = {
        "knn": KnnSpec(k),
        "hybrid": HybridSpec(k, radius),
        "range": RangeSpec(radius, max_neighbors=2 * k),
    }
    identity, pruning, work = {}, {}, {}
    for kind, spec in specs.items():
        a = mono.query(qs, spec)
        b = shard.query(qs, spec)
        if kind == "range":
            same = bool(
                np.array_equal(a.offsets, b.offsets)
                and np.array_equal(a.dists, b.dists)
                and np.array_equal(a.idxs, b.idxs)
                and np.array_equal(a.truncated, b.truncated)
            )
        else:
            same = bool(
                np.array_equal(a.dists, b.dists)
                and np.array_equal(a.idxs, b.idxs)
            )
        identity[kind] = same
        pruning[kind] = _prune_rate(b)
        work[kind] = {"mono_n_tests": int(a.n_tests),
                      "sharded_n_tests": int(b.n_tests)}
        emit(
            f"shards/{kind}",
            _time_best(lambda s=spec: shard.query(qs, s), reps)
            * 1e6 / n_queries,
            f"identity={same} prune_rate={pruning[kind]} "
            f"plan={b.timings['plan']}",
        )

    mono_s = _time_best(lambda: mono.query(qs, KnnSpec(k)), reps)
    shard_s = _time_best(lambda: shard.query(qs, KnnSpec(k)), reps)
    emit(
        "shards/latency_knn",
        shard_s * 1e6 / n_queries,
        f"mono_us={mono_s * 1e6 / n_queries:.1f} "
        f"ratio={shard_s / mono_s:.2f}x",
    )

    stats = shard.stats()
    summary = {
        "n": n,
        "k": k,
        "n_queries": n_queries,
        "n_shards": stats["n_shards"],
        "child_backend": child_backend,
        "shard_sizes": stats["shard_sizes"],
        "merge_identity": identity,
        "pruning_rate": pruning,
        "n_tests": work,
        "latency": {
            "mono_us_per_query": round(mono_s * 1e6 / n_queries, 2),
            "sharded_us_per_query": round(shard_s * 1e6 / n_queries, 2),
            "sharded_over_mono": round(shard_s / mono_s, 3),
        },
        "lifetime_prune_rate": stats["prune_rate"],
    }
    emit(
        "shards/summary",
        shard_s * 1e6 / n_queries,
        f"identity={all(identity.values())} "
        f"knn_prune_rate={pruning['knn']}",
    )
    return summary


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import json

    print(json.dumps(main(), indent=2, default=str))
