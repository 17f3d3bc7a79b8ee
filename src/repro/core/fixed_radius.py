"""Fixed-radius kNN on the cell grid — the analogue of paper Alg. 1 (RT-kNNS).

For every query: locate its grid cell, gather the 3^d one-ring stencil's
bucket contents (static-shape candidate list), compute squared distances in
dense tiles, mask (sentinel / out-of-radius / self), and keep the k smallest.

Returns, per query, the k best (distance, index) pairs found *within the
radius*, the count of in-radius neighbors, and the number of candidate
distance evaluations performed — the TPU equivalent of the paper's
"ray-sphere intersection tests" (their Table 2 metric).

Grid resolution is dynamic (a traced array); only bucket capacity, k and the
query-chunk size are static, and all are padded to powers of two upstream, so
TrueKNN's radius-doubling rounds recompile O(log N) times, not O(rounds).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .grid import Grid, stencil_offsets

__all__ = ["fixed_radius_knn", "fixed_radius_round", "round_chunk",
           "round_slots", "CHUNK_CANDIDATES"]

# Candidate slots (queries x 3^d x cap) one query chunk gathers at once.
# The round's temporaries grow with it, and so does the TPU compiler's time
# for the round, while the gathers' throughput does not: a v5e ran cap-512
# rounds no faster at 4x this many slots per chunk.
CHUNK_CANDIDATES = 1 << 20


def round_chunk(chunk: int, d: int, cap: int) -> int:
    """Query chunk for a grid round: ``chunk`` capped so one chunk gathers
    at most ``CHUNK_CANDIDATES`` slots (a power of two, at least 1)."""
    fit = max(1, CHUNK_CANDIDATES // (3**d * cap))
    return int(min(chunk, 1 << (fit.bit_length() - 1)))


def round_slots(rows: int, chunk: int, d: int, cap: int) -> int:
    """Candidate slots a grid round gathers over ``rows`` query rows taken
    ``chunk`` at a time: each chunk is cut by ``round_chunk`` and padded
    full, and each of its rows gathers 3^d buckets of ``cap`` slots."""
    cb = round_chunk(chunk, d, cap)
    return -(-int(rows) // cb) * cb * 3**d * cap


def _chunk_candidates(
    buckets,  # (H, cap) int32 point ids, sentinel n
    planes,  # d arrays (H, cap) float32: the same slots' coordinates
    origin,
    inv_cell,
    res_arr,  # (d,) int32, dynamic virtual resolution
    offs,  # (S, d) stencil offsets
    q,  # (chunk, d), padded queries have +inf coords
    qid,  # (chunk,) int32
    r2,  # scalar squared radius
    *,
    n: int,
    table_size: int,
    k: int,
):
    """One chunk of grid-stencil candidate search: gather the one-ring
    stencil's bucket rows (ids and coordinate planes), score squared
    distances, keep the k best within ``r2``.  ``n`` is the sentinel id
    (the number of points the grid was built over).  Shared by the
    per-round host loop (``_round_impl``) and the fused multi-round loop
    (``repro.core.fused_loop``) so both trace the *same* ops — bit-identity
    between them holds by construction, not by tolerance.

    Every word a candidate needs arrives in a row gather of ``cap``
    contiguous slots; no point is gathered by id.

    Returns ``(top_d2 (chunk, k), top_i (chunk, k), found (chunk,),
    valid (chunk, n_cand))`` — ``valid`` is the per-candidate
    distance-evaluation mask the caller reduces into its n_tests counter.
    """
    from .grid import cell_coords_of, hash_coords

    d = len(planes)
    cap = buckets.shape[1]
    chunk = q.shape[0]
    n_cand = offs.shape[0] * cap

    qfin = jnp.where(jnp.isfinite(q), q, 0.0)  # keep pad-query math finite
    coords = cell_coords_of(qfin, origin, inv_cell, res_arr)  # (chunk, d)
    nbr = coords[None, :, :] + offs[:, None, :]  # (S, chunk, d)
    h = hash_coords(nbr, table_size)  # (S, chunk)
    # one row gather per array, stencil cell outermost: candidate ids and
    # each axis's coordinates, (S, chunk, cap)
    cand = buckets[h]
    xs = [planes[a][h] for a in range(d)]
    # exact cell-coord match kills hash collisions (and duplicates): the
    # integer compare is our ray-AABB test analogue.  Each candidate's cell
    # is recomputed from its coordinates as ``grid._bucket_order`` binned
    # it.  Candidate cells are clipped into the grid, so a stencil cell
    # outside it matches nothing.
    for a, x in enumerate(xs):
        cell = cell_coords_of(
            jnp.where(jnp.isfinite(x), x, 0.0), origin[a], inv_cell[a],
            res_arr[a],
        )
        cand = jnp.where(cell == nbr[:, :, a:a + 1], cand, n)
    diff = jnp.stack(xs, axis=-1) - q[None, :, None, :]
    d2 = jnp.sum(diff * diff, axis=-1)
    d2 = jnp.nan_to_num(d2, nan=jnp.inf, posinf=jnp.inf)

    def per_query(v):  # (S, chunk, cap) -> (chunk, S * cap), stencil-major
        return jnp.moveaxis(v, 0, 1).reshape(chunk, n_cand)

    cand = per_query(cand)
    d2 = per_query(d2)
    valid = (cand < n) & jnp.isfinite(q[:, :1])  # pad queries don't count
    not_self = cand != qid[:, None]
    within = valid & not_self & (d2 <= r2)
    found = jnp.sum(within, axis=-1)  # (chunk,)
    d2m = jnp.where(within, d2, jnp.inf)
    kk = min(k, n_cand)
    neg_top, arg = jax.lax.top_k(-d2m, kk)
    top_d = -neg_top
    top_i = jnp.take_along_axis(cand, arg, axis=-1)
    top_i = jnp.where(jnp.isfinite(top_d), top_i, n)
    if kk < k:
        top_d = jnp.pad(top_d, ((0, 0), (0, k - kk)), constant_values=jnp.inf)
        top_i = jnp.pad(top_i, ((0, 0), (0, k - kk)), constant_values=n)
    return top_d, top_i, found, valid


@partial(jax.jit, static_argnames=("n", "table_size", "k", "chunk"))
def _round_impl(
    buckets,  # (H, cap) int32 point ids, sentinel n
    planes,  # d arrays (H, cap) float32 coordinate planes
    origin,
    inv_cell,
    res_arr,  # (d,) int32, dynamic virtual resolution
    queries,  # (Q, d), padded queries have +inf coords
    query_ids,  # (Q,) int32 index of query in the points, or n for "no self"
    r2,  # scalar squared radius
    *,
    n: int,
    table_size: int,
    k: int,
    chunk: int,
):
    d = len(planes)
    offs = jnp.asarray(stencil_offsets(d))  # (S, d)

    q_total = queries.shape[0]
    assert q_total % chunk == 0

    def one_chunk(carry, inp):
        q, qid = inp  # (chunk, d), (chunk,)
        top_d, top_i, found, valid = _chunk_candidates(
            buckets, planes, origin, inv_cell, res_arr,
            offs, q, qid, r2, n=n, table_size=table_size, k=k,
        )
        tests = jnp.sum(valid, dtype=jnp.float32)  # distance evals this chunk
        return carry, (top_d, top_i, found, tests)

    qs = queries.reshape(-1, chunk, d)
    qids = query_ids.reshape(-1, chunk)
    _, (td, ti, fc, tests) = jax.lax.scan(one_chunk, None, (qs, qids))
    return (
        td.reshape(q_total, k),
        ti.reshape(q_total, k),
        fc.reshape(q_total),
        tests,
    )


def fixed_radius_round(
    grid: Grid,
    queries,
    query_ids,
    radius: float,
    k: int,
    *,
    chunk: int = 2048,
):
    """One fixed-radius search round over ``grid``'s points (host wrapper;
    shapes made chunk-aligned).

    Returns (dists2 (Q,k), idxs (Q,k), found (Q,), n_tests scalar).
    Entries beyond the in-radius neighbor set have dist=inf, idx=N.
    ``round_slots(Q, chunk, d, grid.cap)`` is the slots it gathers.
    """
    q = jnp.asarray(queries, jnp.float32)
    qid = jnp.asarray(query_ids, jnp.int32)
    q_total = q.shape[0]
    chunk = round_chunk(min(chunk, max(1, q_total)), q.shape[1], grid.cap)
    pad = (-q_total) % chunk
    if pad:
        q = jnp.concatenate([q, jnp.full((pad, q.shape[1]), jnp.inf, q.dtype)])
        qid = jnp.concatenate([qid, jnp.full((pad,), grid.n_points, qid.dtype)])
    d2, idx, found, tests = _round_impl(
        grid.buckets,
        grid.planes,
        grid.origin,
        grid.inv_cell,
        grid.res_arr,
        q,
        qid,
        jnp.float32(radius) ** 2,
        n=grid.n_points,
        table_size=grid.table_size,
        k=int(k),
        chunk=chunk,
    )
    n_tests = int(np.asarray(tests, dtype=np.float64).sum())
    return d2[:q_total], idx[:q_total], found[:q_total], n_tests


def fixed_radius_knn(points, radius, k, *, queries=None, chunk: int = 2048):
    """Deprecated shim: paper Alg. 1 via the registry's "fixed_radius"
    backend (self-excluded when queries are the dataset itself).  Builds a
    throwaway index — and therefore a fresh grid — per call; hold a
    ``build_index(points, backend="fixed_radius", radius=r)`` handle to
    amortize the grid across batches.

    Returns (dists (Q,k), idxs (Q,k), found (Q,), n_tests).
    """
    from repro.api import HybridSpec, build_index
    from repro.api.query import warn_deprecated_once

    warn_deprecated_once(
        "repro.core.fixed_radius.fixed_radius_knn",
        "fixed_radius_knn() is deprecated; use build_index(points, "
        "backend='fixed_radius').query(queries, HybridSpec(k, radius)) and "
        "hold the index across batches",
    )
    res = build_index(
        points, backend="fixed_radius", chunk=chunk
    ).query(queries, HybridSpec(int(k), float(radius)))
    return res.dists, res.idxs, res.found, res.n_tests
