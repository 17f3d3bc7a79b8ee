"""Fused pairwise-distance + streaming top-k Pallas TPU kernel.

This is the compute hot-spot of the TPU adaptation: the role the RT cores'
ray-sphere intersection pipeline plays in the paper.  For a tile of queries
it streams point tiles HBM->VMEM, forms squared distances with the matmul
identity (the cross term runs on the MXU), and maintains a per-query running
top-k candidate buffer in VMEM scratch — so the (Q, N) distance matrix never
touches HBM.  HBM traffic is O(Q·D + N·D·n_qtiles + Q·k) instead of O(Q·N).

Also counts, per query, candidates within ``radius`` (the TrueKNN round
resolution test), fusing the whole fixed-radius round body into one kernel.
That in-radius counter doubles as the native ``RangeSpec`` engine: the count
is the exact ball population, so a range query needs at most one re-run with
``k = counts.max()`` to surface every in-ball neighbor.

Metric dispatch (``metric`` static arg — see ``repro.api.metrics``):
  * ``"l2"``   — the matmul identity keeps the cross term on the MXU
    (d > 8); low-d uses exact per-axis diff accumulation on the VPU.
  * ``"l1"`` / ``"linf"`` — per-axis |diff| accumulation (sum / running
    max) on the VPU; no useful MXU form exists for these, and the paper's
    2-3D domain makes the axis loop short.  Distances (and the radius
    threshold ref) are in raw metric units, NOT squared.
  * cosine never reaches the kernel: the wrapper (``ops.pairwise_topk``)
    normalizes both sides and runs ``"l2"`` (exact monotone reduction).

Layout notes (TPU):
  * feature dim D is zero-padded to a multiple of 128 lanes upstream; the
    cross-term matmul is (TQ, D) @ (D, TP) on the MXU.
  * top-k merge is a repeated-argmin selection network over the VMEM-resident
    concat(running_k, tile) buffer — static k, pure VPU, no sort lowering.
    It only needs monotonicity, so it is metric-agnostic.
  * grid = (q_tiles, p_tiles), p innermost ("arbitrary"), so the running
    buffer carries across point tiles and the final tile writes the output.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_TQ = 256
DEFAULT_TP = 512
# The unrolled selection network keeps O(TQ * (k + TP)) live values in
# VMEM, so the query tile shrinks as k grows to stay inside the 16 MiB
# scoped-VMEM default: TQ * k <= 8192 (v5e compiles k=32 at TQ=256, k=64
# at 128, k=128 at 64, k=256 at 32, all at TP=512 over 2^20 points).
_TQ_TIMES_K = 8192
MAX_K = 256  # largest k rehearsed to compile for v5e

_NEG_LARGE = -jnp.inf


def _topk_merge(buf_d, buf_i, k):
    """k smallest of buf_d (rows) via repeated argmin; returns (TQ,k) pairs."""
    tq, m = buf_d.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (tq, m), 1)
    outs_d, outs_i = [], []
    for _ in range(k):
        j = jnp.argmin(buf_d, axis=1)  # (TQ,)
        sel = col == j[:, None]
        outs_d.append(jnp.min(buf_d, axis=1))
        outs_i.append(jnp.sum(jnp.where(sel, buf_i, 0), axis=1))
        buf_d = jnp.where(sel, jnp.inf, buf_d)
    return jnp.stack(outs_d, axis=1), jnp.stack(outs_i, axis=1)


def _kernel(
    # inputs
    q_ref,  # (TQ, D) queries tile
    qid_ref,  # (TQ, 1) int32 query ids (N_real => "no self")
    p_ref,  # (TP, D) points tile
    r2_ref,  # (1, 1) f32 squared radius
    # outputs
    od_ref,  # (TQ, K) top-k squared distances
    oi_ref,  # (TQ, K) top-k point indices
    oc_ref,  # (TQ, 1) int32 in-radius candidate count
    # scratch
    run_d,  # (TQ, K) f32
    run_i,  # (TQ, K) int32
    run_c,  # (TQ, 1) int32
    *,
    k: int,
    tp: int,
    n_real: int,
    n_p_tiles: int,
    metric: str,
    n_dim: int,
):
    pid_p = pl.program_id(1)

    @pl.when(pid_p == 0)
    def _init():
        run_d[...] = jnp.full_like(run_d, jnp.inf)
        run_i[...] = jnp.full_like(run_i, n_real)
        run_c[...] = jnp.zeros_like(run_c)

    q = q_ref[...]
    p = p_ref[...]
    if metric in ("l1", "linf"):
        # VPU tile path: per-axis |diff| accumulation over the REAL feature
        # dims only (n_dim, not the lane-padded q.shape[1] — padding
        # columns are zero on both sides and would only waste VPU work).
        # d2 here holds RAW metric distances (not squared); r2_ref matches.
        d2 = jnp.zeros((q.shape[0], p.shape[0]), jnp.float32)
        for a in range(min(n_dim, q.shape[1])):
            ad = jnp.abs(q[:, a][:, None] - p[:, a][None, :])
            d2 = d2 + ad if metric == "l1" else jnp.maximum(d2, ad)
    elif n_dim <= 8:
        # low-d (the paper's 2D/3D domain): exact per-axis diff accumulation
        # on the VPU — the matmul identity cancels catastrophically for the
        # tiny squared distances of clustered data, and a d<=8 contraction
        # never profits from the MXU.  Keyed on the REAL dim: on TPU the
        # features are lane-padded to 128, and the zero columns add nothing.
        d2 = jnp.zeros((q.shape[0], p.shape[0]), jnp.float32)
        for a in range(n_dim):
            diff = q[:, a][:, None] - p[:, a][None, :]
            d2 = d2 + diff * diff
    else:
        # ||q-p||^2 = ||q||^2 + ||p||^2 - 2 q.p ; cross term on the MXU.
        qn = jnp.sum(q * q, axis=1, keepdims=True)  # (TQ, 1)
        pn = jnp.sum(p * p, axis=1)  # (TP,)
        cross = jax.lax.dot_general(
            q,
            p,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (TQ, TP)
        d2 = jnp.maximum(qn + pn[None, :] - 2.0 * cross, 0.0)

    gidx = pid_p * tp + jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    valid = gidx < n_real
    not_self = gidx != qid_ref[...]  # (TQ,1) broadcast against (TQ,TP)
    keep = valid & not_self
    d2 = jnp.where(keep, d2, jnp.inf)

    r2 = r2_ref[0, 0]
    run_c[...] += jnp.sum((d2 <= r2) & keep, axis=1, dtype=jnp.int32)[:, None]

    buf_d = jnp.concatenate([run_d[...], d2], axis=1)
    buf_i = jnp.concatenate([run_i[...], gidx], axis=1)
    new_d, new_i = _topk_merge(buf_d, buf_i, k)
    run_d[...] = new_d
    run_i[...] = new_i

    @pl.when(pid_p == n_p_tiles - 1)
    def _flush():
        od_ref[...] = run_d[...]
        oi_ref[...] = run_i[...]
        oc_ref[...] = run_c[...]


@functools.partial(
    jax.jit,
    static_argnames=("k", "tq", "tp", "n_real", "interpret", "metric",
                     "n_dim"),
)
def pairwise_topk_padded(
    queries,  # (Qp, Dp) f32, padded
    query_ids,  # (Qp, 1) int32
    points,  # (Np, Dp) f32, padded
    r2,  # (1, 1) f32 threshold: squared radius for l2, raw for l1/linf
    *,
    k: int,
    n_real: int,
    tq: int = DEFAULT_TQ,
    tp: int = DEFAULT_TP,
    interpret: bool = False,
    metric: str = "l2",
    n_dim: int | None = None,  # real (pre-padding) feature dim
):
    """Pallas call on pre-padded operands.  See ops.pairwise_topk for the
    user-facing wrapper (padding, tile sizes, interpret mode on CPU)."""
    assert metric in ("l2", "l1", "linf"), metric
    if not interpret and k > MAX_K:
        raise ValueError(
            f"pairwise_topk: k={k} exceeds the largest k the kernel compiles "
            f"for (MAX_K={MAX_K})"
        )
    qp, dp = queries.shape
    np_, _ = points.shape
    assert qp % tq == 0 and np_ % tp == 0
    n_q_tiles = qp // tq
    n_p_tiles = np_ // tp

    kernel = functools.partial(
        _kernel, k=k, tp=tp, n_real=n_real, n_p_tiles=n_p_tiles,
        metric=metric, n_dim=dp if n_dim is None else n_dim,
    )
    grid = (n_q_tiles, n_p_tiles)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tq, dp), lambda i, j: (i, 0)),
            pl.BlockSpec((tq, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((tp, dp), lambda i, j: (j, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((tq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((tq, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qp, k), jnp.float32),
            jax.ShapeDtypeStruct((qp, k), jnp.int32),
            jax.ShapeDtypeStruct((qp, 1), jnp.int32),
        ],
        # VMEM-resident running buffers, persistent across the p grid axis
        scratch_shapes=_scratch_shapes(tq, k),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(queries, query_ids, points, r2)


def query_tile(k: int) -> int:
    """Query tile for a top-k width: the largest power of two <= 256 with
    ``tile * k <= 8192`` (never below the 8-row sublane tile)."""
    tq = DEFAULT_TQ
    while tq > 8 and tq * k > _TQ_TIMES_K:
        tq //= 2
    return tq


def _scratch_shapes(tq, k):
    return [
        pltpu.VMEM((tq, k), jnp.float32),
        pltpu.VMEM((tq, k), jnp.int32),
        pltpu.VMEM((tq, 1), jnp.int32),
    ]
