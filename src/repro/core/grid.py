"""Spatial hash grid — the TPU-native analogue of the paper's BVH.

The paper prunes ray-sphere intersection tests with a hardware-traversed BVH
over radius-r spheres.  On TPU, pointer-chasing tree traversal is hostile to
the hardware; the idiomatic equivalent for *fixed-radius* search is a uniform
cell decomposition with cell side >= r: every point within radius r of a query
lies in the 3^d-cell one-ring stencil around the query's cell.

A *dense* cell array collapses on real point clouds (LiDAR: a dense core plus
far outliers stretches the bounding box so a radius-matched dense grid needs
billions of cells).  We therefore use a **spatial hash grid** (Teschner-style):
virtual resolution is radius-matched and unbounded, occupied cells hash into a
table of O(#occupied) buckets, and exactness is preserved by filtering
gathered candidates on an exact match of their integer cell coords,
recomputed from the candidates' own coordinates (the integer-compare plays
the role of the hardware ray-AABB test; hash collisions are filtered, never
double-counted).

Bucket contents are stored bucket-major: next to the ``(H, cap)`` point ids
each grid holds one ``(H, cap)`` float32 coordinate plane per axis, laid
out slot for slot like the ids, so a round reads every word it needs as
contiguous bucket rows instead of gathering point by point.

Binning is a counting sort (O(N)), which plays the role of the paper's BVH
*refit* when the radius grows.  Buckets are fixed-capacity ``(H, cap)`` with
pow2-padded dims so TrueKNN's radius-doubling rounds recompile O(log N)
times, not O(rounds).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Grid", "build_grid", "check_grid_dim", "stencil_offsets",
           "hash_coords"]

# Teschner et al. spatial-hash primes (one per axis).
_HASH_PRIMES = (73856093, 19349663, 83492791)
#: most axes a grid can hash: one prime per axis
MAX_GRID_DIM = len(_HASH_PRIMES)
_MAX_RES_PER_AXIS = 1 << 20  # keeps packed host-side ids within int64


def _next_pow2(x: int) -> int:
    return 1 << max(0, (int(x) - 1).bit_length())


@dataclasses.dataclass(frozen=True)
class Grid:
    """Static-shape spatial hash grid over a point set.

    Attributes:
      buckets:     (H, cap) int32 point indices, padded with N (sentinel).
      planes:      d arrays (H, cap) float32, one per axis: the coordinates
                   of the bucketed points, ``planes[a][h, s] ==
                   points[buckets[h, s], a]``; +inf in sentinel slots.
      origin:      (d,) float32 lower corner of the bounding box.
      inv_cell:    (d,) float32 reciprocal effective cell size per axis.
      res:         (d,) host ints — virtual cells per axis (bounds check only).
      res_arr:     (d,) int32 device copy (dynamic under jit).
      table_size:  int, H (static, pow2).
      cap:         int, bucket capacity (static, pow2).
      n_points:    int.
      cell_size:   (d,) np.float32 effective cell size (>= build radius).
    """

    buckets: jax.Array
    planes: tuple
    origin: jax.Array
    inv_cell: jax.Array
    res: tuple
    res_arr: jax.Array
    table_size: int
    cap: int
    n_points: int
    cell_size: np.ndarray


def check_grid_dim(d: int, engine: str) -> None:
    """Refuse, at build time, a cloud of more axes than a grid hashes;
    ``engine`` names the index being built, for the message."""
    if d > MAX_GRID_DIM:
        raise ValueError(
            f"{engine} hashes points into a grid of at most {MAX_GRID_DIM} "
            f"dimensions, and these points have {d}; use "
            f'backend="brute" (exact dense kNN) for d > {MAX_GRID_DIM}'
        )


def stencil_offsets(d: int) -> np.ndarray:
    """(3^d, d) integer offsets of the one-ring stencil."""
    grids = np.meshgrid(*([np.arange(-1, 2)] * d), indexing="ij")
    return np.stack([g.ravel() for g in grids], axis=-1).astype(np.int32)


def hash_coords(coords, table_size: int):
    """Spatial hash of integer cell coords -> bucket id in [0, table_size).

    Works identically for jnp int32 arrays and np int64/int32 arrays (uint32
    wraparound arithmetic in both).
    """
    if isinstance(coords, jnp.ndarray):
        u = coords.astype(jnp.uint32)
        h = u[..., 0] * jnp.uint32(_HASH_PRIMES[0])
        for a in range(1, coords.shape[-1]):
            h = h ^ (u[..., a] * jnp.uint32(_HASH_PRIMES[a]))
        # table_size may be traced (one sort compile for every grid shape)
        mask = jnp.asarray(table_size - 1).astype(jnp.uint32)
        return (h & mask).astype(jnp.int32)
    u = coords.astype(np.uint32)
    h = u[..., 0] * np.uint32(_HASH_PRIMES[0])
    for a in range(1, coords.shape[-1]):
        h = h ^ (u[..., a] * np.uint32(_HASH_PRIMES[a]))
    return (h & np.uint32(table_size - 1)).astype(np.int64)


def cell_coords_of(points, origin, inv_cell, res_arr):
    """Per-axis integer cell coords, clamped to the virtual grid."""
    c = jnp.floor((points - origin) * inv_cell).astype(jnp.int32)
    return jnp.clip(c, 0, res_arr - 1)


@jax.jit
def _bucket_order(points, origin, inv_cell, res_arr, table_size, n_valid):
    """Stable order of the points by hash bucket, and the sorted bucket ids.

    Rows >= n_valid are padding (sharded grids pad shards to equal length):
    they sort last.  The table size and n_valid are traced, so the one sort
    of the cloud compiles once for every grid over it (a TPU compile of a
    2^20-row sort takes ~20 s).
    """
    n = points.shape[0]
    valid = jnp.arange(n) < n_valid
    coords = cell_coords_of(
        jnp.where(jnp.isfinite(points), points, 0.0), origin, inv_cell, res_arr
    )
    h = jnp.where(valid, hash_coords(coords, table_size), table_size - 1)
    order = jnp.argsort(h).astype(jnp.int32)  # stable
    return order, h[order]


@partial(jax.jit, static_argnames=("table_size", "cap"))
def _fill_buckets(order, sorted_h, points, n_valid, *, table_size, cap):
    """Lay the bucket-sorted points into ``(table_size, cap)`` slots: their
    ids, and their coordinates as one plane per axis in the same slots.
    Also return the fullest bucket's population, so the caller can prove
    no point was dropped for want of a slot."""
    n, d = points.shape
    i = jnp.arange(n, dtype=jnp.int32)
    # rank within own bucket: distance from the bucket's first sorted row
    slot = i - jnp.searchsorted(sorted_h, sorted_h, side="left").astype(
        jnp.int32
    )
    real = i < n_valid  # the stable sort put the padding rows last
    # ascending and unique while every bucket fits its cap; padding rows
    # land past the table and are dropped
    pos = jnp.where(real, sorted_h * cap + slot, table_size * cap + i)

    def scatter(fill, values):
        return jnp.full((table_size * cap,), fill, values.dtype).at[pos].set(
            values, mode="drop", indices_are_sorted=True, unique_indices=True
        ).reshape(table_size, cap)

    buckets = scatter(n, order)
    sorted_pts = points[order]
    # one array per axis: an (H, cap, d) array would pad d to 128 lanes on
    # a TPU, and slicing one (d, H, cap) array inside a round copies it
    planes = tuple(scatter(jnp.inf, sorted_pts[:, a]) for a in range(d))
    fullest = jnp.max(jnp.where(real, slot, -1)) + 1
    return buckets, planes, fullest


def build_grid(
    points,
    radius: float,
    *,
    max_bucket_elems: int = 1 << 25,
    load_factor: float = 0.5,
    force_table_size: int = 0,
    force_cap: int = 0,
    n_valid: int = 0,
    probe_cache: dict = None,
) -> Grid:
    """Build a hash grid whose effective cell size is >= ``radius`` per axis.

    Host-orchestrated (table size / capacity become concrete) — the analogue
    of the paper's host-side BVH refit between rounds.  ``n_valid``: rows
    beyond it are padding (sharded stacking), excluded from the index.

    ``probe_cache``: optional per-point-cloud memo of the table-sizing probe
    below.  The probe is deterministic in (points[:n_valid], initial res),
    and the initial res is itself a pure function of the radius — so a
    caller holding one dict per resident cloud (TrueKNN's lattice rebuilds
    the same snapped radii batch after batch) skips the O(N) host probe on
    repeats.  Ignored under ``force_table_size``/``force_cap`` (the caller
    already owns the shape).  ``"_hits"``/``"_misses"`` count lookups.
    """
    pts_all = np.asarray(points, dtype=np.float32)
    n, d = pts_all.shape
    n_valid = n_valid or n
    pts = pts_all[:n_valid]
    lo = pts.min(axis=0)
    hi = pts.max(axis=0)
    extent = np.maximum(hi - lo, 1e-12)

    radius = float(max(radius, 1e-12))
    res = np.clip(
        np.floor(extent / radius).astype(np.int64), 1, _MAX_RES_PER_AXIS
    )

    use_cache = (
        probe_cache is not None and not force_table_size and not force_cap
    )
    probe_key = (n_valid, tuple(int(x) for x in res)) if use_cache else None
    cached = probe_cache.get(probe_key) if use_cache else None
    if cached is not None:
        probe_cache["_hits"] = probe_cache.get("_hits", 0) + 1
        table_size, cap, res_t = cached
        res = np.asarray(res_t, np.int64)
        cell = (extent / res).astype(np.float32)
    else:
        while True:
            cell = (extent / res).astype(np.float32)
            # the device's float32 arithmetic (cell_coords_of): dividing
            # instead moves boundary points to the next cell, and a bucket
            # the probe undercounts would overflow its cap
            coords = np.clip(
                np.floor((pts - lo) * (np.float32(1) / cell)).astype(np.int64),
                0, res - 1,
            )
            # pack to a unique id per occupied cell (host side, exact)
            packed = coords[:, 0]
            for a in range(1, d):
                packed = packed * res[a] + coords[:, a]
            n_occ = len(np.unique(packed))
            table_size = force_table_size or _next_pow2(
                max(int(n_occ / load_factor), 16)
            )
            while True:
                h = hash_coords(coords.astype(np.int64), table_size)
                occ = np.bincount(h, minlength=table_size)
                needed_cap = _next_pow2(max(int(occ.max()), 1))
                # Over the bucket budget, fold the table before coarsening:
                # the fullest cell sets the cap, and the exact coord match
                # filters the extra collisions, so halving the table halves
                # the buckets while the cap barely moves.  Coarsening instead
                # multiplies every cell's population, and on a skewed cloud
                # of 10^6 points it collapsed the grid to a few cells.
                if (force_table_size or table_size <= 16
                        or table_size * needed_cap <= max_bucket_elems):
                    break
                table_size //= 2
            if force_cap:
                # caller pre-computed a shared shape (sharded-grid stacking);
                # it must be adequate — exactness over silent truncation.
                assert needed_cap <= force_cap, (needed_cap, force_cap)
                cap = force_cap
                break
            cap = needed_cap
            if table_size * cap <= max_bucket_elems or int(res.max()) == 1:
                break
            res = np.maximum(res // 2, 1)  # coarsen (cells grow — always safe)
        if use_cache:
            probe_cache["_misses"] = probe_cache.get("_misses", 0) + 1
            probe_cache[probe_key] = (
                table_size, cap, tuple(int(r) for r in res)
            )

    res_t = tuple(int(r) for r in res)
    origin = jnp.asarray(lo)
    inv_cell = jnp.asarray(np.float32(1) / cell)
    res_arr = jnp.asarray(res_t, jnp.int32)
    pts_dev = jnp.asarray(pts_all)
    order, sorted_h = _bucket_order(
        pts_dev, origin, inv_cell, res_arr, table_size, n_valid
    )
    buckets, planes, fullest = _fill_buckets(
        order, sorted_h, pts_dev, n_valid, table_size=table_size, cap=cap
    )
    if int(fullest) > cap:
        raise RuntimeError(
            f"build_grid: a bucket holds {int(fullest)} points but the probe "
            f"sized buckets for {cap}; binning would drop points"
        )
    return Grid(
        buckets=buckets,
        planes=planes,
        origin=origin,
        inv_cell=inv_cell,
        res=res_t,
        res_arr=res_arr,
        table_size=table_size,
        cap=cap,
        n_points=n,
        cell_size=cell,
    )
