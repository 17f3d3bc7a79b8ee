"""Sharded-grid distributed TrueKNN — the paper's pruning at multi-pod scale.

The dense streaming engine (distributed.py) is exact in one pass but touches
every (query, point-shard) pair: per-round cost Q x N/P.  This module ports
the *candidate-side* pruning too: every point shard builds its own spatial
hash grid (stacked into arrays whose leading shard dim lives on the mesh's
``model`` axis), a fixed-radius round runs per shard through the grid stencil
(O(27·cap) candidates per query instead of N/P), partial in-radius top-k
lists merge across shards with the hypercube exchange, and the TrueKNN
retirement/radius-doubling loop drives rounds from the host — Alg. 3 with
both of its savings intact on 512 chips.

Stacking contract: all shards share (table_size, cap) = max over shards
(computed in a cheap first pass), so the stacked arrays are rectangular; the
per-shard origin/res/cell arrays ride along, so each shard's geometry is its
own.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .fixed_radius import _round_impl
from .grid import build_grid
from .sampling import sample_start_radius


def shard_points(points: np.ndarray, n_shards: int):
    """Split (N, d) row-wise into (n_shards, Nl, d) with +inf padding rows.

    Returns (stacked, n_valid per shard).  Global index of shard s row i is
    s * Nl + i.
    """
    pts = np.asarray(points, np.float32)
    n, d = pts.shape
    nl = -(-n // n_shards)
    out = np.full((n_shards, nl, d), np.inf, np.float32)
    n_valid = np.zeros((n_shards,), np.int64)
    for s in range(n_shards):
        chunk = pts[s * nl : (s + 1) * nl]
        out[s, : len(chunk)] = chunk
        n_valid[s] = len(chunk)
    return out, n_valid


def build_stacked_grids(pts_shards: np.ndarray, n_valid: np.ndarray, radius: float):
    """Per-shard hash grids at a common (table_size, cap) shape.

    Returns a dict of stacked arrays (leading dim = shard) + the shape ints.
    """
    n_shards, nl, d = pts_shards.shape
    reqs = []
    for s in range(n_shards):
        g = build_grid(pts_shards[s], radius, n_valid=int(n_valid[s]))
        reqs.append((g.table_size, g.cap))
    table_size = max(t for t, _ in reqs)
    cap = max(c for _, c in reqs)
    # second pass at the common shape (cap may grow at the shared H; retry)
    while True:
        try:
            grids = [
                build_grid(
                    pts_shards[s],
                    radius,
                    n_valid=int(n_valid[s]),
                    force_table_size=table_size,
                    force_cap=cap,
                )
                for s in range(n_shards)
            ]
            break
        except AssertionError:
            cap *= 2
    stack = lambda xs: jnp.stack(xs)
    return {
        "buckets": stack([g.buckets for g in grids]),
        "planes": tuple(
            stack([g.planes[a] for g in grids]) for a in range(d)
        ),
        "origin": stack([g.origin for g in grids]),
        "inv_cell": stack([g.inv_cell for g in grids]),
        "res": stack([g.res_arr for g in grids]),
    }, table_size, cap


def make_grid_round(mesh: Mesh, k: int, table_size: int, n_local: int, *,
                    chunk: int = 1024, point_axis: str = "model"):
    """shard_map'd fixed-radius round over stacked per-shard grids, each
    built over ``n_local`` point rows (the shard's sentinel id).

    fn(buckets (P,H,cap), planes d x (P,H,cap), origin (P,d),
       inv_cell (P,d), res (P,d), queries (Q,d), query_ids (Q,), r2 ()) ->
       (d2 (Q,k), idx (Q,k) global, found (Q,), tests ())
    """
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    p_size = mesh.shape[point_axis]
    assert p_size & (p_size - 1) == 0

    def local_fn(buckets, planes, origin, inv_cell, res, q_l, qid_l, r2):
        # strip the size-1 shard dim shard_map leaves on sharded operands
        buckets, planes = buckets[0], tuple(p[0] for p in planes)
        origin, inv_cell, res = origin[0], inv_cell[0], res[0]
        n_global = n_local * p_size
        shard = jax.lax.axis_index(point_axis)
        qid_local = jnp.where(
            (qid_l >= shard * n_local) & (qid_l < (shard + 1) * n_local),
            qid_l - shard * n_local,
            n_local,
        ).astype(jnp.int32)
        q_chunk = min(chunk, q_l.shape[0])
        d2, idx, found, tests = _round_impl(
            buckets, planes, origin, inv_cell, res,
            q_l, qid_local, r2,
            n=n_local, table_size=table_size, k=k, chunk=q_chunk,
        )
        idx = jnp.where(
            idx < n_local, idx + shard * n_local, n_global
        ).astype(jnp.int32)

        # hypercube merge of in-radius partial top-k + found counts
        step = 1
        while step < p_size:
            perm = [(i, i ^ step) for i in range(p_size)]
            od2 = jax.lax.ppermute(d2, point_axis, perm)
            oidx = jax.lax.ppermute(idx, point_axis, perm)
            ofound = jax.lax.ppermute(found, point_axis, perm)
            cat_d = jnp.concatenate([d2, od2], axis=1)
            cat_i = jnp.concatenate([idx, oidx], axis=1)
            neg, sel = jax.lax.top_k(-cat_d, k)
            d2 = -neg
            idx = jnp.take_along_axis(cat_i, sel, axis=1)
            found = found + ofound
            step *= 2
        tests_total = jax.lax.psum(
            jnp.sum(tests), (point_axis, *batch_axes)
        )
        return d2, idx, found, tests_total

    qspec = P(batch_axes or None, None)
    gspec = P(point_axis)  # leading shard dim
    return jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(gspec, gspec, gspec, gspec, gspec,
                  qspec, P(batch_axes or None), P()),
        out_specs=(qspec, qspec, P(batch_axes or None), P()),
        check_vma=False,
    )


def distributed_trueknn_grid(
    points,
    k: int,
    mesh: Mesh,
    *,
    queries=None,
    start_radius=None,
    growth: float = 2.0,
    max_rounds: int = 40,
    point_axis: str = "model",
):
    """Full TrueKNN (Alg. 3) over mesh-sharded points with per-shard grids.

    Returns (dists (Q,k), idxs (Q,k) global, stats dict).
    """
    pts = np.asarray(points, np.float32)
    n, d = pts.shape
    p_size = mesh.shape[point_axis]
    shards, n_valid = shard_points(pts, p_size)
    nl = shards.shape[1]

    if queries is None:
        q_all = pts
        qid_all = (np.arange(n, dtype=np.int64)).astype(np.int32)
        # global index of point j is (j // nl) * nl + j % nl == j  (row-major)
    else:
        q_all = np.asarray(queries, np.float32)
        qid_all = np.full((q_all.shape[0],), -1, np.int32)
    q_total = q_all.shape[0]
    r = float(start_radius) if start_radius else sample_start_radius(pts)
    r0 = r

    out_d = np.full((q_total, k), np.inf, np.float32)
    out_i = np.full((q_total, k), n, np.int32)
    alive = np.arange(q_total)
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bsz = int(np.prod([mesh.shape[a] for a in batch_axes])) or 1
    qsh = NamedSharding(mesh, P(batch_axes or None, None))
    idsh = NamedSharding(mesh, P(batch_axes or None))
    gsh = NamedSharding(mesh, P(point_axis))

    stats = {"rounds": [], "total_tests": 0, "start_radius": r0}
    rounds = 0
    while alive.size and rounds < max_rounds:
        grids, table_size, cap = build_stacked_grids(shards, n_valid, r)
        grids = {kk: jax.device_put(v, gsh) for kk, v in grids.items()}
        fn = jax.jit(make_grid_round(mesh, k, table_size, nl,
                                     point_axis=point_axis))

        m = alive.size
        m_pad = max(bsz, 1 << max(0, (m - 1).bit_length()))
        q = np.full((m_pad, d), np.inf, np.float32)
        q[:m] = q_all[alive]
        qid = np.full((m_pad,), -1, np.int32)
        qid[:m] = qid_all[alive]
        d2, idx, found, tests = fn(
            grids["buckets"], grids["planes"], grids["origin"],
            grids["inv_cell"], grids["res"],
            jax.device_put(q, qsh), jax.device_put(qid, idsh),
            jnp.float32(r) ** 2,
        )
        d2 = np.asarray(d2)[:m]
        idx = np.asarray(idx)[:m]
        found = np.asarray(found)[:m]
        tests = float(np.asarray(tests))
        stats["total_tests"] += int(tests)
        resolved = found >= k
        done = alive[resolved]
        out_d[done] = d2[resolved]
        out_i[done] = idx[resolved]
        alive = alive[~resolved]
        stats["rounds"].append(
            {"radius": r, "queries": m, "resolved": int(resolved.sum()),
             "tests": int(tests), "cap": cap, "table": table_size}
        )
        r *= growth
        rounds += 1

    assert alive.size == 0, f"{alive.size} unresolved after {max_rounds} rounds"
    # translate padded-shard global idx back to dataset idx (identity while
    # n % p == 0; otherwise padded rows never match — idx < n guaranteed)
    return np.sqrt(np.maximum(out_d, 0)), out_i, stats
