"""Graph-workloads benchmark: kNN-graph / DBSCAN identity, self-batch
locality.

Measures, at bench scale:

* **graph identity** — ``build_knn_graph`` must produce bit-identical CSR
  arrays (``indptr`` / ``indices`` / ``dists``, ``np.array_equal``) from
  brute, trueknn, sharded and placed indexes over the same cloud.
* **dbscan identity** — ``dbscan`` labels and core masks likewise
  bit-stable across all four backends.
* **self-batch locality** — on a blob dataset whose morton partition
  aligns shard == blob, the sharded ``AllPairsSpec`` pre-pass must
  resolve rows shard-locally (``self_local_rows``) and keep shared-cut
  visits to boundary rows only; the summary reports the resolved
  fraction and the visit counts, and the gate asserts the pruning
  engaged.
* **throughput** — rows/s for graph construction and clustering on each
  backend (reported honestly; on CPU the fabric's dispatch overhead can
  lose to one fused monolithic pass — identity + work reduction are the
  contract, latency is the record).

Emits CSV rows via the harness contract and returns a summary dict that
benchmarks/run.py serializes to BENCH_graph.json.
"""

from __future__ import annotations

import time

import numpy as np

from repro.api import build_index
from repro.core import make_dataset
from repro.workloads import build_knn_graph, dbscan

from .common import emit


def _blobs(n: int, n_blobs: int, dim: int = 3, scale: float = 1.0):
    """``n_blobs`` unit-scale gaussian blobs along the space diagonal:
    the morton partition's equal-count cut aligns shard == blob, the
    geometry where the self-batch pre-pass proves rows interior."""
    rng = np.random.default_rng(0)
    per = n // n_blobs
    return np.concatenate([
        np.full(dim, 100.0 * i, np.float32)
        + rng.normal(scale=scale, size=(per, dim)).astype(np.float32)
        for i in range(n_blobs)
    ])


def _indexes(pts, n_shards):
    return {
        "brute": build_index(pts, backend="brute"),
        "trueknn": build_index(pts, backend="trueknn"),
        "sharded": build_index(pts, backend="sharded", n_shards=n_shards),
        "placed": build_index(
            pts, backend="sharded", n_shards=n_shards, placement="devices"
        ),
    }


def main(n=4_000, k=8, n_shards=8, eps_quantile=60.0) -> dict:
    # -- identity at bench scale on the clustered paper dataset ------------
    pts = make_dataset("porto", n, seed=0)
    idxs = _indexes(pts, n_shards)

    graphs, gtimes = {}, {}
    for name, idx in idxs.items():
        t0 = time.perf_counter()
        graphs[name] = build_knn_graph(idx, k)
        gtimes[name] = time.perf_counter() - t0
        emit(
            f"graph/build/{name}",
            gtimes[name] * 1e6 / n,
            f"edges={graphs[name].n_edges} rows_per_s={n / gtimes[name]:.0f}",
        )
    ref = graphs["brute"]
    graph_identity = {
        name: bool(
            np.array_equal(ref.indptr, g.indptr)
            and np.array_equal(ref.indices, g.indices)
            and np.array_equal(ref.dists, g.dists)
        )
        for name, g in graphs.items()
    }

    # eps from the graph itself: the given percentile of k-th-NN distance
    kth = ref.dists[ref.indptr[1:] - 1]
    eps = float(np.percentile(kth, eps_quantile))
    clusterings, ctimes = {}, {}
    for name, idx in idxs.items():
        t0 = time.perf_counter()
        clusterings[name] = dbscan(idx, eps, k)
        ctimes[name] = time.perf_counter() - t0
        emit(
            f"graph/dbscan/{name}",
            ctimes[name] * 1e6 / n,
            f"clusters={clusterings[name].n_clusters} "
            f"noise={clusterings[name].n_noise}",
        )
    cref = clusterings["brute"]
    dbscan_identity = {
        name: bool(
            np.array_equal(cref.labels, c.labels)
            and np.array_equal(cref.core, c.core)
        )
        for name, c in clusterings.items()
    }

    # -- self-batch locality on blob-aligned shards ------------------------
    bpts = _blobs(n, n_shards)
    blob_idx = build_index(bpts, backend="sharded", n_shards=n_shards)
    bg = build_knn_graph(blob_idx, k)
    st = blob_idx.stats()
    q_total = len(bpts)
    local = int(st["self_local_rows"])
    boundary = int(st["self_boundary_rows"])
    visits = int(st["shard_visits"])
    # visits beyond the per-row local pre-pass can only come from
    # boundary rows' shared-cut rounds
    cut_visits = visits - q_total
    local_frac = round(local / q_total, 4)
    blob_ref = build_knn_graph(build_index(bpts, backend="brute"), k)
    blob_identity = bool(
        np.array_equal(bg.indptr, blob_ref.indptr)
        and np.array_equal(bg.indices, blob_ref.indices)
        and np.array_equal(bg.dists, blob_ref.dists)
    )
    emit(
        "graph/self_local",
        0.0,
        f"local={local}/{q_total} boundary={boundary} "
        f"cut_visits={cut_visits} identity={blob_identity}",
    )

    summary = {
        "n": n,
        "k": k,
        "n_shards": n_shards,
        "eps": eps,
        "edges": int(ref.n_edges),
        "clusters": int(cref.n_clusters),
        "noise": int(cref.n_noise),
        "graph_identity": graph_identity,
        "dbscan_identity": dbscan_identity,
        "rows_per_s": {
            "graph": {m: round(n / t, 1) for m, t in gtimes.items()},
            "dbscan": {m: round(n / t, 1) for m, t in ctimes.items()},
        },
        "self_batch": {
            "rows": q_total,
            "self_local_rows": local,
            "self_boundary_rows": boundary,
            "local_fraction": local_frac,
            "shard_visits": visits,
            "shared_cut_visits": cut_visits,
            "identity": blob_identity,
        },
        "gates": {
            # bit-stable artifacts from every backend
            "graph_identity": all(graph_identity.values()),
            "dbscan_identity": all(dbscan_identity.values()),
            # measured shard-local pruning: on blob-aligned shards at
            # least 90% of rows resolve in the local pre-pass and the
            # shared-cut rounds touch only boundary rows
            "self_local_pruning": (
                local_frac >= 0.9
                and cut_visits <= boundary * n_shards
                and blob_identity
            ),
        },
    }
    emit(
        "graph/summary",
        gtimes["sharded"] * 1e6 / n,
        f"graph_identity={summary['gates']['graph_identity']} "
        f"dbscan_identity={summary['gates']['dbscan_identity']} "
        f"self_local={local_frac}",
    )
    return summary


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import json

    print(json.dumps(main(), indent=2, default=str))
