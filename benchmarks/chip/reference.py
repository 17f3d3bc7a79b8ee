"""The plain reference and the comparison that decides ``correct``.

The reference is a float64 NumPy brute force over the whole cloud, adapted
from ``chip_smoke.py`` (``_over_blocks`` / ``_check_lists``); it imports
nothing of the system under test.  ``compare`` turns a set of returned kNN
rows into the numbers that the limits hold:

  unfilled_rows  rows with a non-finite distance, an id outside the cloud,
                 or an id listed twice (exact: limit 0);
  kth_err        max |returned k-th distance - true k-th distance| / true;
  dist_err       max |returned distance - that id's true distance| / the
                 row's true k-th distance;
  rank_err       max (true distance of a returned id - true k-th distance)
                 / true k-th distance, 0 when every returned id is among
                 the true k nearest (ties at the k-th distance included).

``bf16_knn`` is the control: the same brute force computed in bfloat16,
one precision below the configuration's float32, put in the program's
place.  It has to fail the limits.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# a block's distance matrix holds at most this many values (128 MiB of
# float64); a block needs about two such matrices at once
BLOCK_VALUES = 1 << 24


def _over_blocks(fn, n_rows, n_points, threads=None):
    """Run ``fn(row slice)`` over query blocks on a few host threads (NumPy
    releases the GIL in its array loops); results come back in order."""
    rows = max(1, BLOCK_VALUES // max(n_points, 1))
    blocks = [slice(s, min(s + rows, n_rows)) for s in range(0, n_rows, rows)]
    threads = threads or min(8, os.cpu_count() or 1)
    with ThreadPoolExecutor(max_workers=threads) as ex:
        return list(ex.map(fn, blocks))


def _sq_dists64(pts64, q64):
    """(m, n) float64 squared distances."""
    d2 = np.zeros((len(q64), len(pts64)))
    tmp = np.empty_like(d2)
    for a in range(pts64.shape[1]):
        np.subtract(q64[:, a:a + 1], pts64[:, a], out=tmp)
        np.multiply(tmp, tmp, out=tmp)
        d2 += tmp
    return d2


def true_kth(pts, queries, k):
    """float64 distance of each query's true k-th nearest point."""
    pts64 = np.asarray(pts, np.float64)
    q64 = np.asarray(queries, np.float64)

    def block(sl):
        d2 = _sq_dists64(pts64, q64[sl])
        return np.sqrt(np.partition(d2, k - 1, axis=1)[:, k - 1])

    parts = _over_blocks(block, len(q64), len(pts64))
    return np.concatenate(parts) if parts else np.empty((0,))


def compare(pts, queries, dists, idxs, k) -> dict:
    """The compared numbers for returned rows ``dists``/``idxs`` (m, k) of
    ``queries`` (m, d) against the whole cloud ``pts``."""
    pts = np.asarray(pts)
    n = len(pts)
    dists = np.asarray(dists, np.float64).reshape(len(queries), -1)
    idxs = np.asarray(idxs).reshape(len(queries), -1)
    ok = np.isfinite(dists).all(1) & (dists.shape[1] == k)
    ok &= ((idxs >= 0) & (idxs < n)).all(1)
    srt = np.sort(idxs, axis=1)
    ok &= (np.diff(srt, axis=1) != 0).all(1)
    out = {"rows_checked": int(len(queries)),
           "unfilled_rows": int((~ok).sum()),
           "kth_err": 0.0, "dist_err": 0.0, "rank_err": 0.0}
    if not ok.any():
        return out
    q64 = np.asarray(queries, np.float64)[ok]
    d = dists[ok]
    ids = idxs[ok]
    kth = true_kth(pts, q64, k)
    diff = np.asarray(pts, np.float64)[ids] - q64[:, None, :]
    id_d = np.sqrt(np.sum(diff * diff, axis=-1))  # true distance of each id
    scale = np.maximum(kth, 1e-30)
    out["kth_err"] = float(np.max(np.abs(d[:, k - 1] - kth) / scale))
    out["dist_err"] = float(np.max(np.abs(d - id_d) / scale[:, None]))
    out["rank_err"] = float(
        np.max(np.maximum(id_d.max(1) - kth, 0.0) / scale)
    )
    return out


def _bf16(x):
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32
    )


def bf16_knn(pts, queries, k, threads=None):
    """The control: brute-force kNN with every input and every arithmetic
    result rounded to bfloat16.  Returns (dists (m, k), idxs (m, k)),
    computed on ``threads`` host threads."""
    p = _bf16(pts)
    q = _bf16(queries)

    def block(sl):
        qb = q[sl]
        d2 = np.zeros((len(qb), len(p)), np.float32)
        for a in range(p.shape[1]):
            diff = _bf16(qb[:, a:a + 1] - p[:, a])
            d2 = _bf16(d2 + _bf16(diff * diff))
        part = np.argpartition(d2, k - 1, axis=1)[:, :k]
        pd2 = np.take_along_axis(d2, part, axis=1)
        order = np.argsort(pd2, axis=1, kind="stable")
        ids = np.take_along_axis(part, order, axis=1)
        return _bf16(np.sqrt(np.take_along_axis(pd2, order, axis=1))), ids

    parts = _over_blocks(block, len(q), len(p), threads)
    return (np.concatenate([a for a, _ in parts]),
            np.concatenate([b for _, b in parts]))


def judge(numbers: dict, limits: dict) -> bool:
    """True when rows were checked and every number is within its limit."""
    if numbers.get("rows_checked", 0) <= 0:
        return False
    return all(numbers[name] <= limit for name, limit in limits.items())
