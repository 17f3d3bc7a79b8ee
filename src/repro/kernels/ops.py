"""User-facing jit'd wrappers for the Pallas kernels.

On a TPU backend the kernels compile to Mosaic.  On the CPU backend (tests,
CI) they execute with ``interpret=True`` — Pallas's Python interpreter —
which runs the same kernel body over the same BlockSpec pipeline.  Any other
backend raises: there is no silent fallback to the interpreter or to the jnp
reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .pairwise_topk import DEFAULT_TP, pairwise_topk_padded, query_tile

__all__ = ["pairwise_topk", "l2_normalize"]


def _interpret() -> bool:
    """False on TPU (Mosaic), True on CPU (the Pallas interpreter); any
    other backend has no kernel path and raises."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"pairwise_topk: no Pallas kernel path on backend {backend!r} "
        "(TPU compiles it, CPU interprets it)"
    )


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def l2_normalize(x):
    """Unit-normalize rows (jnp), 1e-12 floor on the norm.  The ONE device-
    side implementation of the cosine reduction's transform — the brute
    engine imports it, and api.metrics.normalize_rows is its NumPy twin
    (keep the epsilon and zero-row semantics in sync across all three)."""
    n = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))
    return x / jnp.maximum(n, 1e-12)


def pairwise_topk(
    queries,
    points,
    k: int,
    *,
    radius: float = np.inf,
    query_ids=None,
    metric: str = "l2",
    tq: int | None = None,
    tp: int | None = None,
):
    """Exact k smallest distances from each query to the point set, plus the
    count of points within ``radius`` — fused, streaming, O(Q·k) output
    memory.  The engine of the brute / distributed search paths and (via
    the counter) the native ``RangeSpec`` engine.

    ``metric`` selects the distance ("l2", "l1", "linf", "cosine" — see
    ``repro.api.metrics``).  ``radius`` is always in metric units.

    Returns (d (Q, k) f32, idx (Q, k) i32, counts (Q,) i32), rows sorted
    nearest-first.  For ``metric="l2"`` ``d`` holds SQUARED distances (the
    historical contract every existing caller relies on); for every other
    metric ``d`` holds true metric distances.  ``idx`` is N for slots
    beyond the point count.  ``query_ids`` (Q,) optionally excludes one
    self index per query.
    """
    q = jnp.asarray(queries, jnp.float32)
    p = jnp.asarray(points, jnp.float32)
    n_q, d = q.shape
    n_real = p.shape[0]
    assert p.shape[1] == d
    interpret = _interpret()

    r = float(radius)
    if metric == "cosine":
        # exact monotone L2 reduction: normalize, search L2, map back.
        q = l2_normalize(q)
        p = l2_normalize(p)
        kernel_metric = "l2"
        # d_cos <= r  <=>  ||q̂-p̂||² <= 2r ; cosine distance caps at 2.
        thr = 2.0 * min(r, 2.0) if np.isfinite(r) else np.inf
    elif metric in ("l1", "linf"):
        kernel_metric = metric
        thr = r if np.isfinite(r) else np.inf  # raw threshold in-kernel
    elif metric == "l2":
        kernel_metric = "l2"
        thr = np.float32(r) ** 2 if np.isfinite(r) else np.inf
    else:
        raise ValueError(f"pairwise_topk: unsupported metric {metric!r}")

    tq = tq or min(query_tile(int(k)), _round_up(n_q, 8))
    tp = tp or min(DEFAULT_TP, _round_up(n_real, 128))
    # lane-align features on TPU; the interpreter only needs sublane width
    dp = _round_up(max(d, 1), 8 if interpret else 128)

    qp = _round_up(n_q, tq)
    np_pad = _round_up(n_real, tp)
    q_pad = jnp.zeros((qp, dp), jnp.float32).at[:n_q, :d].set(q)
    p_pad = jnp.zeros((np_pad, dp), jnp.float32).at[:n_real, :d].set(p)
    if query_ids is None:
        qid = jnp.full((qp, 1), n_real, jnp.int32)
    else:
        qid = jnp.full((qp, 1), n_real, jnp.int32).at[:n_q, 0].set(
            jnp.asarray(query_ids, jnp.int32)
        )
    r2 = jnp.asarray([[thr]], jnp.float32)
    d_out, idx, counts = pairwise_topk_padded(
        q_pad,
        qid,
        p_pad,
        r2,
        k=int(k),
        n_real=int(n_real),
        tq=tq,
        tp=tp,
        interpret=interpret,
        metric=kernel_metric,
        n_dim=d,
    )
    d_out = d_out[:n_q]
    if metric == "cosine":
        d_out = d_out * 0.5  # squared L2 on normalized rows -> cosine dist
    return d_out, idx[:n_q], counts[:n_q, 0]
