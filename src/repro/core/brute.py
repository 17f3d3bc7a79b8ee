"""Exact brute-force kNN oracle (the role cuML's kNN plays in the paper).

Chunked over queries so the (Q, N) distance matrix never materializes whole.
Used (a) as the correctness oracle for every other search path, (b) as the
non-accelerated comparison point (paper Fig. 4), (c) as the exact
subroutine inside start-radius sampling (paper Alg. 2 uses sklearn), and
(d) as the exact tail of TrueKNN's multi-round driver.

``brute_knn_engine`` is the raw engine; the public ``brute_knn`` is a
deprecated shim over ``repro.api.build_index(..., backend="brute")`` kept
for the pre-index call sites.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ops import l2_normalize

__all__ = ["brute_knn", "brute_knn_engine"]


@partial(jax.jit, static_argnames=("metric",))
def _chunk_dists(points, p_norm2, q, *, metric):
    """(chunk, N) distances of one query chunk: squared L2, or raw L1/Linf.

    A jit of its own so that, traced inside a scope of the chunk loop, the
    reducer regions of its sums carry no scope: JAX names a region by the
    loop body's own scope path, without the callers' prefixes, and the
    fused program's readers expect every scope in it to nest under theirs.
    """
    d = points.shape[1]
    if metric in ("l1", "linf"):
        # raw metric distances — no squaring, no sqrt downstream
        ad = jnp.abs(q[:, None, :] - points[None, :, :])
        return jnp.sum(ad, axis=-1) if metric == "l1" else jnp.max(ad, -1)
    if d <= 8:
        # exact diff-based form: the matmul identity loses ~1e-7 absolute
        # to cancellation, which is catastrophic for the tiny squared
        # distances of tightly-clustered data (and d<=8 never profits
        # from the MXU anyway)
        diff = q[:, None, :] - points[None, :, :]
        return jnp.sum(diff * diff, axis=-1)
    q_norm2 = jnp.sum(q * q, axis=-1)
    # HIGHEST: at the default precision a TPU rounds the operands of a
    # float32 matmul to bfloat16, a 2^-9 relative error per query
    # coordinate in the cross term; the distances are stated in float32
    cross = jnp.matmul(q, points.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(q_norm2[:, None] + p_norm2[None, :] - 2.0 * cross, 0.0)


@partial(jax.jit, static_argnames=("k", "chunk", "exclude_self", "metric"))
def _brute_impl(points, queries, query_ids, *, k, chunk, exclude_self, metric):
    n = points.shape[0]
    d = points.shape[1]
    q_total = queries.shape[0]
    assert q_total % chunk == 0
    p_norm2 = jnp.sum(points * points, axis=-1)  # (N,)

    def one_chunk(_, inp):
        q, qid = inp
        with jax.named_scope("brute.dist"):
            d2 = _chunk_dists(points, p_norm2, q, metric=metric)
            if exclude_self:
                d2 = jnp.where(jnp.arange(n)[None, :] == qid[:, None],
                               jnp.inf, d2)
        with jax.named_scope("brute.select"):
            neg, idx = jax.lax.top_k(-d2, k)
        return None, (-neg, idx)

    qs = queries.reshape(-1, chunk, d)
    qids = query_ids.reshape(-1, chunk)
    _, (td, ti) = jax.lax.scan(one_chunk, None, (qs, qids))
    return td.reshape(q_total, k), ti.reshape(q_total, k)


def brute_knn_engine(
    points, k, *, queries=None, query_ids=None, chunk: int = 512,
    metric: str = "l2",
):
    """Exact kNN engine.  Returns (dists (Q,k), idxs (Q,k), n_tests).

    ``queries`` None: the dataset queries itself, self-matches excluded (the
    paper's setting).  ``query_ids`` (with explicit ``queries``): global
    point index of each query for self-exclusion — pass N (or any
    out-of-range id) for queries that are not dataset members.  This is how
    TrueKNN's brute tail keeps self-exclusion for still-alive self-queries.

    ``metric`` picks the distance ("l2", "l1", "linf", "cosine"); returned
    dists are always true metric-space values (the l2 sqrt, the cosine
    ``ℓ²/2`` map and the raw l1/linf forms all happen in here).
    """
    pts = jnp.asarray(points, jnp.float32)
    if metric == "cosine":
        pts = l2_normalize(pts)  # exact monotone L2 reduction
    elif metric not in ("l2", "l1", "linf"):
        raise ValueError(f"brute_knn_engine: unsupported metric {metric!r}")
    n = pts.shape[0]
    if queries is None:
        q = pts
        qid = jnp.arange(n, dtype=jnp.int32)
        exclude_self = True
        k_cap = n - 1
    else:
        q = jnp.asarray(queries, jnp.float32)
        if metric == "cosine":
            q = l2_normalize(q)
        if query_ids is None:
            qid = jnp.full((q.shape[0],), n, jnp.int32)
            exclude_self = False
            k_cap = n
        else:
            qid = jnp.asarray(query_ids, jnp.int32)
            exclude_self = True
            k_cap = n  # member queries must request k <= N-1 upstream
    q_total = q.shape[0]
    chunk = int(min(chunk, max(1, q_total)))
    pad = (-q_total) % chunk
    if pad:
        q = jnp.concatenate([q, jnp.zeros((pad, q.shape[1]), q.dtype)])
        qid = jnp.concatenate([qid, jnp.full((pad,), n, qid.dtype)])
    k_eff = min(int(k), k_cap)
    impl_metric = "l2" if metric == "cosine" else metric
    d2, idx = _brute_impl(
        pts, q, qid, k=k_eff, chunk=chunk, exclude_self=exclude_self,
        metric=impl_metric,
    )
    d2, idx = d2[:q_total], idx[:q_total]
    if k_eff < k:
        d2 = jnp.pad(d2, ((0, 0), (0, k - k_eff)), constant_values=jnp.inf)
        idx = jnp.pad(idx, ((0, 0), (0, k - k_eff)), constant_values=n)
    n_tests = q_total * n
    if metric == "l2":
        d_out = jnp.sqrt(d2)
    elif metric == "cosine":
        d_out = d2 * 0.5  # squared L2 on normalized rows -> cosine distance
    else:
        d_out = d2  # l1 / linf: already raw metric distances
    return d_out, idx, n_tests


def brute_knn(points, k, *, queries=None, chunk: int = 512):
    """Deprecated shim: exact kNN via the registry's "brute" backend.

    Returns (dists (Q,k), idxs (Q,k), n_tests) — the historical tuple.
    Prefer ``build_index(points, backend="brute").query(queries, KnnSpec(k))``
    and hold the index across batches.
    """
    from repro.api import KnnSpec, build_index
    from repro.api.query import warn_deprecated_once

    warn_deprecated_once(
        "repro.core.brute.brute_knn",
        "brute_knn() is deprecated; use build_index(points, backend='brute')"
        ".query(queries, KnnSpec(k)) and hold the index across batches",
    )
    res = build_index(points, backend="brute", chunk=chunk).query(
        queries, KnnSpec(int(k))
    )
    return res.dists, res.idxs, res.n_tests
