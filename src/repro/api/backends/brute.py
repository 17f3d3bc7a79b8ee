"""Brute-force backend — the exact oracle behind ``backend="brute"``.

Wraps the chunked jit-compiled engine in ``repro.core.brute``; "building"
the index is just pinning the cloud, but repeated queries still amortize
jit compilation across batches (shapes are stable per batch size).

Every registered metric is native here (the dense engines dispatch on the
metric tag), and range queries run on the fused Pallas kernel's in-radius
counter — the counts are exact ball populations, so a range answer costs
at most two kernel passes.
"""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro import trace
from repro.core.brute import brute_knn_engine
from repro.core.result import KNNResult, RangeResult

from ..index import NeighborIndex
from ..metrics import Metric
from ..query import HybridSpec, KnnSpec, RangeSpec
from ..registry import register_backend

__all__ = ["BruteIndex"]


@register_backend("brute")
class BruteIndex(NeighborIndex):
    """Exact kNN by chunked dense distances.

    cfg: ``chunk`` (query tile, default 512).  ``stats()["pair_tests"]``
    counts the query-point pairs the kNN engine scored (sum of Q·N).
    """

    native_metrics = frozenset({"l2", "l1", "linf", "cosine"})
    knn_start_radius_semantics = "bound"  # no schedule: it's a post-filter

    def __init__(self, points, *, chunk: int = 512):
        super().__init__(points)
        self._chunk = int(chunk)
        self._pts_j = jnp.asarray(self._pts)  # device-resident for the life
        self._queries_served = 0
        self._pair_tests = 0  # query-point pairs the dense engine scored

    def _knn(self, queries, k: int, metric: Metric, *, cut=None):
        t0 = time.perf_counter()
        with trace.span("brute.dispatch"):
            d, i, n_tests = brute_knn_engine(
                self._pts_j, k, queries=queries, chunk=self._chunk,
                metric=metric.kernel_name,
            )
        with trace.span("brute.fetch"):
            dists = np.asarray(d)
            idxs = np.asarray(i)
        found = None
        if cut is not None:
            # radius cap: drop beyond-radius hits.  NOTE: the engine only
            # surfaces the top-k, so ``found`` counts in-radius neighbors
            # among those k (capped at k) — the full ball population comes
            # from the range path's kernel counter instead.
            from ..planner import apply_radius_cut

            dists, idxs, found = apply_radius_cut(
                dists, idxs, cut, self.n_points
            )
        self._queries_served += dists.shape[0]
        self._pair_tests += int(n_tests)
        return KNNResult(
            dists=dists,
            idxs=idxs,
            n_tests=int(n_tests),
            backend=self.backend_name,
            metric=metric.name,
            found=found,
            timings={"query_seconds": time.perf_counter() - t0},
        )

    def execute_knn(self, queries, spec: KnnSpec, metric: Metric,
                    ctx=None) -> KNNResult:
        if spec.stop_radius is not None:
            raise ValueError("brute backend has no radius schedule; "
                             "stop_radius is not meaningful here")
        # start_radius on a schedule-free engine: convenience post-filter
        # (backend-defined semantics, same as PR 1's ``radius=``).
        return self._knn(queries, spec.k, metric, cut=spec.start_radius)

    def execute_hybrid(self, queries, spec: HybridSpec, metric: Metric,
                       ctx=None):
        return self._knn(queries, spec.k, metric, cut=spec.radius)

    def execute_range(self, queries, spec: RangeSpec, metric: Metric,
                      ctx=None):
        from ..planner import range_via_counted_topk

        res = range_via_counted_topk(
            self._pts, queries, spec, metric, backend=self.backend_name
        )
        self._queries_served += res.n_queries
        return res

    def stats(self) -> dict:
        s = super().stats()
        s["queries_served"] = self._queries_served
        s["pair_tests"] = self._pair_tests
        return s
