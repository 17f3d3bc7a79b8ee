"""Device-placement benchmark: fused-dispatch proof, identity, latency.

The placed sharded fabric (``placement="devices"``) pins each shard's
point block to a mesh device and runs every shared-cut round as ONE
device-parallel dispatch; the host fabric runs the same round as S
sequential child queries.  This benchmark proves the three acceptance
gates at bench scale and records them in the summary for CI:

* **one dispatch per round** — counter-proven: a placed hybrid batch
  reports ``fused_dispatches == 1`` while the host fabric burns one
  child dispatch per visited shard (``child_dispatches`` delta == the
  batch's shard visits); placed kNN reports at most one fused dispatch
  per search round.
* **identity** — placed answers are ``np.array_equal`` to the monolithic
  oracle on every spec kind (dists, idxs, offsets, truncation flags).
* **latency** — fusing the round is worth real wall-clock: a placed
  hybrid batch must run at most 0.6x the sequential host fabric, and
  stay within 1.5x of the monolithic index.

The monolith and both fabrics use the trueknn engine (the repo default,
and the engine whose float forms the placed path reproduces exactly —
the brute oracle's range distances differ at the ULP level).  Runs on
whatever device count the process booted with: the chips of a TPU host,
or on CPU the host devices that
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` creates (CI sets
it).

Emits CSV rows via the harness contract and returns a summary dict that
benchmarks/run.py serializes to BENCH_placement.json.
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


def _time_best(fn, reps: int) -> float:
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def main(n=20_000, k=8, n_queries=512, n_shards=8, reps=3) -> dict:
    import jax

    pts = make_dataset("porto", n, seed=0)
    rng = np.random.default_rng(1)
    qs = (
        pts[rng.integers(0, n, n_queries)]
        + rng.normal(scale=0.05, size=(n_queries, pts.shape[1]))
    ).astype(np.float32)

    mono = build_index(pts, backend="trueknn")
    host = build_index(
        pts, backend="sharded", n_shards=n_shards, placement="host",
    )
    placed = build_index(
        pts, backend="sharded", n_shards=n_shards, placement="devices",
    )
    # warm pass: sampling, jit for every index/spec shape
    warm = mono.query(qs, KnnSpec(k))
    host.query(qs, KnnSpec(k))
    placed.query(qs, KnnSpec(k))
    radius = warm_default_radius(warm.dists, mono)

    # --- gate 1: one fused dispatch per round, counter-proven vs S host
    h_before = host.stats()["child_dispatches"]
    h = host.query(qs, HybridSpec(k, radius))
    host_dispatches = host.stats()["child_dispatches"] - h_before
    p = placed.query(qs, HybridSpec(k, radius))
    placed_dispatches = p.timings["fused_dispatches"]
    # host burns one child dispatch per shard that survives the cull;
    # placed folds every surviving shard into the one fused program
    one_dispatch = bool(
        placed_dispatches == 1
        and 1 < host_dispatches <= n_shards
        and h.timings["shard_visits"] > 0
    )
    pk = placed.query(qs, KnnSpec(k))
    knn_per_round = pk.timings["fused_dispatches"] / max(pk.n_rounds, 1)
    one_dispatch = one_dispatch and knn_per_round <= 1.0
    emit(
        "placement/dispatches",
        placed_dispatches,
        f"placed_hybrid={placed_dispatches} host_hybrid={host_dispatches} "
        f"knn_per_round={knn_per_round:.2f} proven={one_dispatch}",
    )

    # --- gate 2: bit-identity vs the monolithic oracle
    specs = {
        "knn": KnnSpec(k),
        "hybrid": HybridSpec(k, radius),
        "range": RangeSpec(radius, max_neighbors=2 * k),
    }
    identity = {}
    for kind, spec in specs.items():
        a = mono.query(qs, spec)
        b = placed.query(qs, spec)
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
        emit(
            f"placement/{kind}",
            _time_best(lambda s=spec: placed.query(qs, s), reps)
            * 1e6 / n_queries,
            f"identity={same} plan={b.timings['plan']}",
        )

    # --- gate 3: fusing the round pays on the wall clock
    hspec = HybridSpec(k, radius)
    mono_s = _time_best(lambda: mono.query(qs, hspec), reps)
    host_s = _time_best(lambda: host.query(qs, hspec), reps)
    placed_s = _time_best(lambda: placed.query(qs, hspec), reps)
    vs_host = placed_s / host_s
    vs_mono = placed_s / mono_s
    emit(
        "placement/latency_hybrid",
        placed_s * 1e6 / n_queries,
        f"host_us={host_s * 1e6 / n_queries:.1f} "
        f"mono_us={mono_s * 1e6 / n_queries:.1f} "
        f"vs_host={vs_host:.2f}x vs_mono={vs_mono:.2f}x",
    )

    ps = placed.stats()["placement"]
    summary = {
        "n": n,
        "k": k,
        "n_queries": n_queries,
        "n_shards": n_shards,
        "devices": len(jax.devices()),
        "slots": ps["slots"],
        "device_occupancy": ps["device_occupancy"],
        "dispatches": {
            "placed_hybrid": int(placed_dispatches),
            "host_hybrid": int(host_dispatches),
            "placed_knn_per_round": round(knn_per_round, 4),
        },
        "identity": identity,
        "latency": {
            "mono_us_per_query": round(mono_s * 1e6 / n_queries, 2),
            "host_us_per_query": round(host_s * 1e6 / n_queries, 2),
            "placed_us_per_query": round(placed_s * 1e6 / n_queries, 2),
            "placed_over_host": round(vs_host, 3),
            "placed_over_mono": round(vs_mono, 3),
        },
        "gates": {
            "one_dispatch_per_round": one_dispatch,
            "identity": bool(all(identity.values())),
            "placed_le_0p6x_host": bool(vs_host <= 0.6),
            "placed_le_1p5x_mono": bool(vs_mono <= 1.5),
        },
    }
    emit(
        "placement/summary",
        placed_s * 1e6 / n_queries,
        " ".join(f"{g}={v}" for g, v in summary["gates"].items()),
    )
    return summary


if __name__ == "__main__":
    import json

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    print(json.dumps(main(), indent=2, default=str))
