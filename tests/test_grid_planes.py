"""Bucket-major coordinate planes: the grid stores each bucket's point
coordinates slot for slot beside its ids, and a grid round reads them as
bucket rows instead of gathering points and cells by id.

The oracle below is the element-gather round the planes replaced, kept
here only to prove the two give bit-identical answers.
"""

import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import KnnSpec, build_index
from repro.core.datasets import make_dataset
from repro.core.fixed_radius import _chunk_candidates, round_chunk, round_slots
from repro.core.grid import (
    build_grid,
    cell_coords_of,
    hash_coords,
    stencil_offsets,
)

ROOT = Path(__file__).resolve().parents[1]
K = 6


def _element_gather_candidates(points_padded, buckets, point_cells, origin,
                               inv_cell, res_arr, offs, q, qid, r2, *,
                               table_size, k):
    """The round before the planes: ids from the bucket rows, then each
    id's cell coords and coordinates gathered point by point."""
    n = points_padded.shape[0] - 1
    cap = buckets.shape[1]
    chunk = q.shape[0]
    n_cand = offs.shape[0] * cap
    qfin = jnp.where(jnp.isfinite(q), q, 0.0)
    coords = cell_coords_of(qfin, origin, inv_cell, res_arr)
    nbr = coords[:, None, :] + offs[None, :, :]
    in_range = jnp.all((nbr >= 0) & (nbr < res_arr), axis=-1)
    h = hash_coords(nbr, table_size)
    cand = jnp.where(in_range[..., None], buckets[h], n)
    ccell = point_cells[cand]
    match = jnp.all(ccell == nbr[:, :, None, :], axis=-1)
    cand = jnp.where(match, cand, n).reshape(chunk, n_cand)
    cpts = points_padded[cand]
    diff = cpts - q[:, None, :]
    d2 = jnp.sum(diff * diff, axis=-1)
    d2 = jnp.nan_to_num(d2, nan=jnp.inf, posinf=jnp.inf)
    valid = (cand < n) & jnp.isfinite(q[:, :1])
    not_self = cand != qid[:, None]
    within = valid & not_self & (d2 <= r2)
    found = jnp.sum(within, axis=-1)
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


def _point_cells(pts, grid):
    """(N+1, d) cell coords of every point as the grid binned it, with the
    sentinel row -2 (matches nothing)."""
    c = cell_coords_of(jnp.asarray(pts), grid.origin, grid.inv_cell,
                       grid.res_arr)
    return jnp.concatenate(
        [c, jnp.full((1, pts.shape[1]), -2, jnp.int32)], axis=0
    )


def _skewed(n, seed):
    """A dense Gaussian core, a thin shell of far outliers and exact
    duplicates: buckets far fuller than the mean."""
    rng = np.random.default_rng(seed)
    core = rng.normal(0.0, 0.05, (n - n // 8, 3))
    far = rng.uniform(-20.0, 20.0, (n // 16, 3))
    dup = core[: n - len(core) - len(far)]
    return np.concatenate([core, far, dup]).astype(np.float32)


def _lidar(n):
    config = json.loads(
        (ROOT / "benchmarks/chip/configs/kitti-lidar-2p20.json").read_text()
    )
    spec = importlib.util.spec_from_file_location(
        "lidar_street_cloud", ROOT / "benchmarks/chip/clouds/lidar_street.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.make(n, 3, int(config["data_seed"]), config["cloud"])


# cloud, radius, grid options, whether the queries are the cloud itself
CASES = {
    "skewed": (lambda: _skewed(3000, 1), 0.04, {}, False),
    "skewed_self": (lambda: _skewed(3000, 2), 0.02, {}, True),
    "folded_table": (lambda: make_dataset("kitti", 3000, seed=4), 0.6,
                     {"force_table_size": 16}, False),
    "folded_table_self": (lambda: make_dataset("uniform", 2000, seed=5),
                          0.05, {"force_table_size": 32}, True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_planes_round_is_bit_identical_to_the_element_gather_round(case):
    make, radius, opts, self_query = CASES[case]
    pts = make()
    n, d = pts.shape
    grid = build_grid(pts, radius, **opts)
    rng = np.random.default_rng(7)
    rows = rng.choice(n, 192, replace=False)
    if self_query:
        q, qid = pts[rows], rows.astype(np.int32)
    else:
        q = pts[rows] + rng.normal(0, radius / 3, (192, d)).astype(np.float32)
        qid = np.full((192,), n, np.int32)
    # +inf padding rows, as a round pads a batch to its chunk
    q = np.concatenate([q, np.full((64, d), np.inf, np.float32)])
    qid = np.concatenate([qid, np.full((64,), n, np.int32)])
    offs = jnp.asarray(stencil_offsets(d))
    r2 = jnp.float32(radius) ** 2
    args = (grid.origin, grid.inv_cell, grid.res_arr, offs, jnp.asarray(q),
            jnp.asarray(qid), r2)

    new = jax.jit(
        lambda *a: _chunk_candidates(
            grid.buckets, grid.planes, *a, n=n,
            table_size=grid.table_size, k=K,
        )
    )(*args)
    pts_padded = jnp.concatenate(
        [jnp.asarray(pts), jnp.full((1, d), jnp.inf, jnp.float32)]
    )
    old = jax.jit(
        lambda *a: _element_gather_candidates(
            pts_padded, grid.buckets, _point_cells(pts, grid), *a,
            table_size=grid.table_size, k=K,
        )
    )(*args)
    (nd, ni, nf, nv), (od, oi, of, ov) = new, old
    np.testing.assert_array_equal(np.asarray(nd), np.asarray(od))
    np.testing.assert_array_equal(np.asarray(ni), np.asarray(oi))
    np.testing.assert_array_equal(np.asarray(nf), np.asarray(of))
    assert int(np.asarray(nv).sum()) == int(np.asarray(ov).sum())
    # the case exercises what it is named for
    assert np.isfinite(np.asarray(nd)[:192, 0]).mean() > 0.5
    assert not np.asarray(nv)[192:].any()
    if opts.get("force_table_size"):
        # more occupied cells than buckets: hash collisions; and 27 stencil
        # cells over at most 32 buckets: cells of one stencil share buckets
        cells = np.asarray(_point_cells(pts, grid))[:n]
        assert len(np.unique(cells, axis=0)) > grid.table_size
        qc = np.asarray(cell_coords_of(jnp.asarray(q[:192]), grid.origin,
                                       grid.inv_cell, grid.res_arr))
        h = hash_coords((qc[:, None, :] + stencil_offsets(d)).astype(np.int64),
                        grid.table_size)
        assert any(len(np.unique(row)) < len(row) for row in h)


@pytest.mark.parametrize("case", sorted(CASES))
def test_planes_hold_the_bucketed_coordinates_slot_for_slot(case):
    make, radius, opts, _ = CASES[case]
    pts = make()
    n, d = pts.shape
    grid = build_grid(pts, radius, **opts)
    buckets = np.asarray(grid.buckets)
    assert len(grid.planes) == d
    for plane in grid.planes:
        assert plane.shape == (grid.table_size, grid.cap)
        assert plane.dtype == jnp.float32
    planes = np.stack([np.asarray(p) for p in grid.planes])
    padded = np.concatenate([pts, np.full((1, d), np.inf, np.float32)])
    for a in range(d):
        np.testing.assert_array_equal(planes[a], padded[buckets][..., a])
    assert np.all(np.isposinf(planes[:, buckets == n]))
    assert np.isfinite(planes[:, buckets < n]).all()


@pytest.mark.parametrize("radius", [0.059, 0.946])
def test_cells_recomputed_from_planes_equal_the_binning_cells(radius):
    """Every point of a 2^16-point HDL-64E street cloud: the cell the round
    recomputes from the planes is the cell the grid binned it by."""
    pts = _lidar(1 << 16)
    n, d = pts.shape
    grid = build_grid(pts, radius)
    buckets = np.asarray(grid.buckets)
    occupied = buckets < n
    ids = buckets[occupied]
    assert np.array_equal(np.sort(ids), np.arange(n))

    @jax.jit
    def from_planes(planes, origin, inv_cell, res_arr):
        x = jnp.stack(planes, axis=-1)  # (H, cap, d), as the round stacks
        return cell_coords_of(
            jnp.where(jnp.isfinite(x), x, 0.0), origin, inv_cell, res_arr
        )

    got = np.asarray(
        from_planes(grid.planes, grid.origin, grid.inv_cell, grid.res_arr)
    )[occupied]
    binned = np.asarray(_point_cells(pts, grid))[ids]
    np.testing.assert_array_equal(got, binned)
    rows = np.nonzero(occupied)[0]
    np.testing.assert_array_equal(
        hash_coords(got.astype(np.int64), grid.table_size), rows
    )


@pytest.mark.parametrize("fused", [True, False])
def test_rounds_report_the_slots_they_gathered(fused):
    pts = make_dataset("kitti", 4000, seed=3)
    index = build_index(pts, backend="trueknn", fused=fused, chunk=256)
    res = index.query(pts[:700] + 0.01, KnnSpec(K))
    grid_rounds = [r for r in res.rounds if np.isfinite(r.radius)]
    assert len(grid_rounds) >= 2
    for r in grid_rounds:
        # the fused loop chunks the live rows; the host loop pads them to
        # a power of two first
        rows = r.n_queries if fused else 1 << (r.n_queries - 1).bit_length()
        chunk = min(256, 1024 if fused else rows)
        cb = round_chunk(chunk, 3, r.grid_cap)
        assert r.n_slots == -(-rows // cb) * cb * 27 * r.grid_cap
        assert r.n_slots == round_slots(rows, chunk, 3, r.grid_cap)
        assert 0 < r.n_tests <= r.n_slots
    s = index.stats()
    assert s["round_slots"] == sum(r.n_slots for r in grid_rounds)
    assert s["round_tests"] == sum(r.n_tests for r in grid_rounds)
