"""Mutation benchmark: the LSM mutable index under write storms.

Measures and asserts, on one ``backend="mutable"`` composite:

* **storm identity** — a randomized insert/delete storm with inline
  compactions; at checkpoints every served answer (dists/idxs/CSR/
  truncated/found, all four metrics x knn/range/hybrid) must be
  bit-identical to a fresh monolithic brute rebuild over the same
  logical snapshot (``map_to_stable`` lifts the rebuild's positional
  idxs into stable-id space).  One checkpoint runs *mid-compaction*:
  the ``_on_compact_built`` seam parks the rebuild after the new base
  is built but before the swap, while reads keep answering from the
  pre-swap snapshot.
* **sustained throughput** — an interleaved insert+query loop at serving
  shape (trueknn base); reports inserts/s and queries/s sustained while
  the log grows, seals and compacts underneath.
* **delta-path read tax** — warm read latency of the composite carrying
  a delta log of ~10% of base rows (compaction off) vs a frozen
  monolithic index over the same live cloud.  The gate is ratio <= 2x:
  riding the log must stay cheaper than rebuilding per write.

Emits CSV rows via the harness contract and returns a summary dict that
benchmarks/run.py serializes to BENCH_mutation.json (a CI artifact next
to the other BENCH_*.json files).
"""

from __future__ import annotations

import threading
import time

import numpy as np

from repro.api import (
    HybridSpec,
    KnnSpec,
    RangeSpec,
    RangeResult,
    build_index,
    make_mutable,
    map_to_stable,
)
from repro.core import make_dataset

from .common import emit, timed

METRICS = ("l2", "l1", "linf", "cosine")


def _same(a, b) -> bool:
    """Bitwise equality of two results of the same spec kind."""
    if isinstance(a, RangeResult):
        return (
            np.array_equal(a.offsets, b.offsets)
            and np.array_equal(a.idxs, b.idxs)
            and np.array_equal(a.dists, b.dists)
            and (
                np.array_equal(a.truncated, b.truncated)
                if a.truncated is not None and b.truncated is not None
                else a.truncated is None and b.truncated is None
            )
        )
    return (
        np.array_equal(a.dists, b.dists)
        and np.array_equal(a.idxs, b.idxs)
        and (
            np.array_equal(a.found, b.found)
            if a.found is not None and b.found is not None
            else a.found is None and b.found is None
        )
    )


def _check_identity(mut, qs, specs) -> dict:
    """Every (metric, spec) answer vs a monolithic brute rebuild over the
    same logical snapshot; returns {metric/spec: bool}."""
    live_pts, live_ids = mut.snapshot()
    mono = build_index(live_pts, backend="brute")
    out = {}
    for metric in METRICS:
        for name, spec in specs:
            got = mut.query(qs, spec, metric=metric)
            want = map_to_stable(
                mono.query(qs, spec, metric=metric), live_ids, mut.sentinel
            )
            out[f"{metric}/{name}"] = _same(got, want)
    return out


def _storm(rng, pts, qs, specs, *, ops, checkpoints) -> dict:
    """Randomized insert/delete storm over a brute-base composite with
    aggressive inline compaction; identity-checks at checkpoints."""
    n, d = pts.shape
    mut = build_index(
        pts,
        backend="mutable",
        base_backend="brute",
        delta_rows=max(48, n // 16),
        compact_min_rows=max(96, n // 8),
        compact_ratio=0.1,
        tombstone_ratio=0.1,
        auto_compact="inline",
    )
    pool = list(range(n))
    checks: dict = {}
    every = max(1, ops // checkpoints)
    for op in range(ops):
        if pool and rng.random() < 0.4:
            take = int(min(len(pool), 1 + rng.integers(0, 16)))
            sel = sorted(
                map(int, rng.choice(len(pool), size=take, replace=False)),
                reverse=True,
            )
            mut.delete([pool.pop(i) for i in sel])
        else:
            m = int(1 + rng.integers(0, 32))
            rows = (
                pts[rng.integers(0, n, m)]
                + rng.normal(scale=0.05, size=(m, d))
            ).astype(np.float32)
            pool.extend(int(i) for i in mut.insert(rows))
        if (op + 1) % every == 0:
            checks.update(_check_identity(mut, qs, specs))
    st = mut.stats()
    return {
        "ops": ops,
        "identity": checks,
        "all_identical": bool(checks) and all(checks.values()),
        "compactions": st["compactions"],
        "final_rows": st["n_points"],
    }


def _mid_compaction(rng, pts, qs, specs) -> dict:
    """Identity while a compaction is parked between build and swap."""
    n, d = pts.shape
    mut = build_index(
        pts, backend="mutable", base_backend="brute",
        delta_rows=max(32, n // 16), auto_compact="off",
    )
    mut.insert(
        (pts[rng.integers(0, n, n // 4)]
         + rng.normal(scale=0.05, size=(n // 4, d))).astype(np.float32)
    )
    mut.delete(rng.choice(n, size=n // 10, replace=False))
    built = threading.Event()
    release = threading.Event()

    def parked(_index):
        built.set()
        release.wait(timeout=120)

    mut._on_compact_built = parked
    t = threading.Thread(target=mut.compact, daemon=True)
    t.start()
    assert built.wait(timeout=120), "compaction never reached the seam"
    try:
        checks = _check_identity(mut, qs, specs)  # pre-swap snapshot serves
        mid_compacting = mut.stats()["compacting"]
    finally:
        release.set()
        t.join()
    mut._on_compact_built = None
    post = _check_identity(mut, qs, specs)  # post-swap must agree too
    return {
        "mid_identity": checks,
        "mid_all_identical": all(checks.values()),
        "was_compacting": bool(mid_compacting),
        "post_identity_ok": all(post.values()),
        "compactions": mut.stats()["compactions"],
    }


def _sustained(rng, pts, k, *, ops, rows_per_insert, n_queries) -> dict:
    """Interleaved insert+query loop at serving shape (trueknn base)."""
    n, d = pts.shape
    mut = make_mutable(
        build_index(pts, backend="trueknn"),
        delta_rows=max(128, n // 32),
        compact_min_rows=max(256, n // 16),
        compact_ratio=0.1,
        auto_compact="inline",
    )
    spec = KnnSpec(k)
    qs = pts[rng.integers(0, n, n_queries)] + rng.normal(
        scale=0.5, size=(n_queries, d)
    ).astype(np.float32)
    mut.query(qs, spec)  # warm: grid builds + jit for the shape buckets
    inserted = 0
    t0 = time.perf_counter()
    for _ in range(ops):
        rows = (
            pts[rng.integers(0, n, rows_per_insert)]
            + rng.normal(scale=0.05, size=(rows_per_insert, d))
        ).astype(np.float32)
        mut.insert(rows)
        inserted += rows_per_insert
        mut.query(qs, spec)
    wall = time.perf_counter() - t0
    st = mut.stats()
    return {
        "ops": ops,
        "rows_inserted": inserted,
        "queries_run": ops * n_queries,
        "wall_s": round(wall, 3),
        "inserts_per_s": round(inserted / wall, 1),
        "queries_per_s": round(ops * n_queries / wall, 1),
        "compactions": st["compactions"],
        "final_rows": st["n_points"],
    }


def _delta_tax(rng, pts, k, *, n_queries, delta_frac=0.10) -> dict:
    """Warm read latency: composite with a ~10%-of-base delta log vs a
    frozen monolith over the same live cloud."""
    n, d = pts.shape
    extra = (
        pts[rng.integers(0, n, int(n * delta_frac))]
        + rng.normal(scale=0.05, size=(int(n * delta_frac), d))
    ).astype(np.float32)
    qs = pts[rng.integers(0, n, n_queries)] + rng.normal(
        scale=0.5, size=(n_queries, d)
    ).astype(np.float32)
    spec = KnnSpec(k)

    mut = make_mutable(
        build_index(pts, backend="trueknn"),
        delta_rows=max(64, extra.shape[0] // 2),
        auto_compact="off",
    )
    mut.insert(extra)
    live_pts, _ = mut.snapshot()
    frozen = build_index(live_pts, backend="trueknn")

    _, t_frozen = timed(lambda: frozen.query(qs, spec), repeats=3)
    _, t_delta = timed(lambda: mut.query(qs, spec), repeats=3)
    st = mut.stats()
    return {
        "base_rows": st["base_rows"],
        "delta_rows": st["delta_rows"],
        "delta_frac": round(st["delta_rows"] / st["base_rows"], 3),
        "frozen_us": round(t_frozen * 1e6, 1),
        "delta_us": round(t_delta * 1e6, 1),
        "ratio": round(t_delta / t_frozen, 3),
    }


def main(n=6000, k=8, storm_n=1200, storm_ops=48, checkpoints=4,
         sustained_ops=24, n_queries=192) -> dict:
    pts = make_dataset("kitti", n, seed=0)
    rng = np.random.default_rng(2)

    storm_pts = pts[:storm_n]
    qs = storm_pts[rng.integers(0, storm_n, 64)] + rng.normal(
        scale=0.5, size=(64, pts.shape[1])
    ).astype(np.float32)
    # radius sized off the base cloud's kth-NN spread so range/hybrid rows
    # are non-trivially populated and max_neighbors actually truncates
    warm = build_index(storm_pts, backend="brute").query(qs, KnnSpec(k))
    r = float(np.median(warm.dists[:, -1]))
    specs = [
        ("knn", KnnSpec(k)),
        ("range", RangeSpec(r, max_neighbors=2 * k)),
        ("hybrid", HybridSpec(k, r)),
    ]

    storm = _storm(rng, storm_pts, qs, specs, ops=storm_ops,
                   checkpoints=checkpoints)
    emit(
        "mutation/storm",
        0.0,
        f"ops={storm['ops']} all_identical={storm['all_identical']} "
        f"compactions={storm['compactions']}",
    )

    mid = _mid_compaction(rng, storm_pts, qs, specs)
    emit(
        "mutation/mid_compaction",
        0.0,
        f"identical={mid['mid_all_identical']} "
        f"was_compacting={mid['was_compacting']}",
    )

    sustained = _sustained(rng, pts, k, ops=sustained_ops,
                           rows_per_insert=64, n_queries=n_queries)
    emit(
        "mutation/sustained",
        sustained["wall_s"] * 1e6 / max(sustained["ops"], 1),
        f"inserts_per_s={sustained['inserts_per_s']} "
        f"queries_per_s={sustained['queries_per_s']} "
        f"compactions={sustained['compactions']}",
    )

    tax = _delta_tax(rng, pts, k, n_queries=n_queries)
    emit(
        "mutation/delta_tax",
        tax["delta_us"],
        f"frozen_us={tax['frozen_us']} ratio={tax['ratio']} "
        f"delta_frac={tax['delta_frac']}",
    )

    return {
        "n": n,
        "k": k,
        "storm": storm,
        "mid_compaction": mid,
        "sustained": sustained,
        "delta_tax": tax,
    }


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import json

    print(json.dumps(main(), indent=2, default=str))
