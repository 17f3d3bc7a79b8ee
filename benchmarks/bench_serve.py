"""Serving benchmark: the NeighborServer front-end under open-loop load.

Measures, on one resident trueknn index:

* **throughput vs offered load** — Poisson arrivals (one query point per
  request) at increasing request rates; for each load the achieved
  throughput, request-latency p50/p99 and the batch-size histogram are
  recorded.  Microbatching shows up as the mean batch size growing with
  offered load (arrivals queue while a batch is in flight, the next batch
  coalesces them) while per-request latency degrades gracefully.
* **served == direct** — the same queries answered through the server and
  through ``index.query`` directly must be identical; the summary carries
  the check so CI can assert on it.
* **cache** — a second pass over the same arrival set, all hits.

Emits CSV rows via the harness contract and returns a summary dict that
benchmarks/run.py serializes to BENCH_serve.json (uploaded as a CI
artifact next to BENCH_index.json / BENCH_query_plans.json).
"""

from __future__ import annotations

import time

import numpy as np

from repro.api import KnnSpec, NeighborServer, build_index
from repro.api.server import poisson_open_loop
from repro.core import make_dataset

from .common import emit


def main(n=16_000, k=8, requests_per_load=192,
         offered_loads=(200.0, 800.0, 3200.0)) -> dict:
    pts = make_dataset("kitti", n, seed=0)
    rng = np.random.default_rng(1)
    spec = KnnSpec(k)

    index = build_index(pts, backend="trueknn")
    qs = pts[rng.integers(0, n, requests_per_load)] + rng.normal(
        scale=0.5, size=(requests_per_load, pts.shape[1])
    ).astype(np.float32)

    # warm pass: sampling, grid builds, jit for the shape buckets
    index.query(qs, spec)

    # -- served results must equal direct query ----------------------------
    direct = index.query(qs, spec)
    check_server = NeighborServer(index, cache_size=0)
    half = requests_per_load // 2
    ta = check_server.submit(qs[:half], spec)
    tb = check_server.submit(qs[half:], spec)
    ra, rb = ta.result(), tb.result()
    served_matches_direct = bool(
        np.array_equal(np.vstack([ra.dists, rb.dists]), direct.dists)
        and np.array_equal(np.vstack([ra.idxs, rb.idxs]), direct.idxs)
    )
    coalesced = int(ra.timings["server_batch_rows"])

    # -- throughput vs offered load ----------------------------------------
    loads = {}
    for rate in offered_loads:
        server = NeighborServer(index, cache_size=0)
        _, wall, lat = poisson_open_loop(server, qs, spec, rate, rng)
        bucket = server.stats()["buckets"][f"default/knn/k={k}/l2"]
        cell = {
            "offered_per_s": rate,
            "achieved_per_s": round(requests_per_load / wall, 1),
            "latency_p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 2),
            "latency_p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 2),
            "mean_batch_rows": bucket["mean_batch_rows"],
            "batch_size_hist": bucket["batch_size_hist"],
            "batches": bucket["batches"],
        }
        loads[str(int(rate))] = cell
        emit(
            f"serve/open_loop/rate={int(rate)}",
            float(np.percentile(lat, 50)) * 1e6,
            f"achieved={cell['achieved_per_s']}/s "
            f"mean_batch={cell['mean_batch_rows']} "
            f"p99_ms={cell['latency_p99_ms']}",
        )

    # -- cache pass --------------------------------------------------------
    server = NeighborServer(index, cache_size=4 * requests_per_load)
    for i in range(len(qs)):
        server.submit(qs[i], spec)
    server.drain()
    before = server.stats()["cache"]  # priming pass: all misses
    t0 = time.perf_counter()
    tickets = [server.submit(qs[i], spec) for i in range(len(qs))]
    for t in tickets:
        t.result()
    cache_wall = time.perf_counter() - t0
    after = server.stats()["cache"]
    # hit rate of the replay pass alone, not the lifetime counters (which
    # include the priming misses and would read ~0.5 forever)
    looked = (after["hits"] - before["hits"]) + (
        after["misses"] - before["misses"]
    )
    hit_rate = round((after["hits"] - before["hits"]) / looked, 4)
    emit(
        "serve/cache_pass",
        cache_wall * 1e6 / requests_per_load,
        f"hit_rate={hit_rate}",
    )

    summary = {
        "n": n,
        "k": k,
        "requests_per_load": requests_per_load,
        "served_matches_direct": served_matches_direct,
        "coalesced_batch_rows": coalesced,
        "loads": loads,
        "cache_pass": {
            "us_per_request": round(cache_wall * 1e6 / requests_per_load, 2),
            "hit_rate": hit_rate,
        },
        "server_stats": server.stats(),
    }
    emit(
        "serve/summary",
        loads[str(int(offered_loads[-1]))]["latency_p50_ms"] * 1e3,
        f"served_matches_direct={served_matches_direct} "
        f"max_load_mean_batch="
        f"{loads[str(int(offered_loads[-1]))]['mean_batch_rows']}",
    )
    return summary


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import json

    print(json.dumps(main(), indent=2, default=str))
