"""Index reuse: build-once/query-many serving vs rebuild-per-batch.

This is the measurement the ``NeighborIndex`` API exists for.  A resident
TrueKNN index serves a stream of query batches; batch 0 pays start-radius
sampling, grid construction and jit compilation, while later batches reuse
the radius-lattice grid cache and warm-start their start radius from the
resolved-radius EMA.  The acceptance bar: every batch after the first runs
strictly faster than batch 0, with the round/build counters proving *why*
(cache hits > 0, builds -> 0, fewer rounds).

A rebuild-per-batch loop over the same batches (fresh index each time —
the pre-API serving pattern, jit-warm) is timed as the baseline.

Emits CSV rows via the harness contract and returns a summary dict that
benchmarks/run.py serializes to BENCH_index.json for cross-PR tracking.
"""

from __future__ import annotations

import time

import numpy as np

from repro.api import KnnSpec, build_index
from repro.core import make_dataset

from .common import emit


def _batches(pts, n_batches, batch_size, seed=1):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_batches):
        qs = pts[rng.integers(0, len(pts), batch_size)] + rng.normal(
            scale=0.5, size=(batch_size, pts.shape[1])
        ).astype(np.float32)
        out.append(qs)
    return out

def main(n=20_000, n_batches=4, batch_size=512, k=8) -> dict:
    pts = make_dataset("kitti", n, seed=0)
    batches = _batches(pts, n_batches, batch_size)

    # -- serving loop on one resident index --------------------------------
    index = build_index(pts, backend="trueknn")
    reuse_ms, rounds, builds, hits = [], [], [], []
    for b, qs in enumerate(batches):
        t0 = time.perf_counter()
        res = index.query(qs, KnnSpec(k))
        dt = (time.perf_counter() - t0) * 1e3
        reuse_ms.append(dt)
        rounds.append(res.n_rounds)
        builds.append(res.timings["grid_builds"])
        hits.append(res.timings["grid_cache_hits"])
        emit(
            f"index_reuse/batch={b}",
            dt * 1e3,
            f"rounds={res.n_rounds} builds={res.timings['grid_builds']} "
            f"hits={res.timings['grid_cache_hits']} "
            f"start={res.timings['start_radius_source']}",
        )

    # -- rebuild-per-batch baseline (the old serving pattern, jit-warm) ----
    rebuild_ms = []
    for qs in batches:
        t0 = time.perf_counter()
        build_index(pts, backend="trueknn").query(qs, KnnSpec(k))
        rebuild_ms.append((time.perf_counter() - t0) * 1e3)

    warm = reuse_ms[1:]
    summary = {
        "n": n,
        "batch_size": batch_size,
        "k": k,
        "reuse_batch_ms": [round(x, 2) for x in reuse_ms],
        "rebuild_batch_ms": [round(x, 2) for x in rebuild_ms],
        "rounds_per_batch": rounds,
        "grid_builds_per_batch": builds,
        "grid_cache_hits_per_batch": hits,
        "warm_below_batch0": bool(warm and max(warm) < reuse_ms[0]),
        "speedup_batch0_over_warm_p50": (
            round(reuse_ms[0] / float(np.median(warm)), 2) if warm else None
        ),
        "speedup_vs_rebuild_p50": round(
            float(np.median(rebuild_ms[1:] or rebuild_ms))
            / float(np.median(warm or reuse_ms)), 2
        ),
        "index_stats": index.stats(),
    }
    emit(
        "index_reuse/summary",
        float(np.median(warm or reuse_ms)) * 1e3,
        f"warm_below_batch0={summary['warm_below_batch0']} "
        f"speedup_vs_rebuild={summary['speedup_vs_rebuild_p50']}x "
        f"warm_builds={sum(builds[1:])} warm_hits={sum(hits[1:])}",
    )
    return summary


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import json

    print(json.dumps(main(), indent=2, default=str))
