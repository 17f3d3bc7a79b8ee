"""Correctness tests for the TrueKNN core (grid, fixed-radius, multi-round)."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    brute_knn,
    build_grid,
    fixed_radius_knn,
    make_dataset,
    max_knn_distance,
    sample_start_radius,
    trueknn,
)
from repro.core.grid import hash_coords, stencil_offsets


def exact_knn_np(pts: np.ndarray, k: int):
    """Float64 oracle, self-excluded."""
    p = pts.astype(np.float64)
    d = np.sqrt(((p[:, None, :] - p[None, :, :]) ** 2).sum(-1))
    np.fill_diagonal(d, np.inf)
    idx = np.argsort(d, axis=1)[:, :k]
    return np.take_along_axis(d, idx, axis=1), idx


def assert_knn_equal(pts, got_idx, k, rtol=1e-5):
    """Compare by distance values (ties in index are legitimate)."""
    td, _ = exact_knn_np(pts, k)
    p = pts.astype(np.float64)
    for r in range(pts.shape[0]):
        gd = np.sort(np.sqrt(((p[got_idx[r]] - p[r]) ** 2).sum(-1)))
        np.testing.assert_allclose(gd, td[r], rtol=rtol, atol=1e-9)


# ---------------------------------------------------------------- grid


def test_grid_bins_every_point_exactly_once():
    pts = make_dataset("porto", 2000, seed=3)
    g = build_grid(pts, 0.01)
    b = np.asarray(g.buckets).ravel()
    real = b[b < g.n_points]
    assert len(real) == 2000
    assert len(np.unique(real)) == 2000


def test_grid_probe_agrees_with_device_binning_on_cell_boundaries():
    """At radius 0.1 over an extent of 6.3 the cell is float32(0.1), and
    x = 1.3 lies in cell 12 by division but in cell 13 by the float32
    reciprocal the device multiplies with.  32 points there and 32 inside
    cell 13: a probe that divides sizes buckets for 32, and binning would
    drop 32 points."""
    x = np.array([0.0, 6.3] + [1.3] * 32 + [1.35] * 32, np.float32)
    pts = np.stack([x, np.zeros_like(x)], -1)
    g = build_grid(pts, 0.1)
    assert g.cap >= 64
    b = np.asarray(g.buckets).ravel()
    assert np.array_equal(np.sort(b[b < len(pts)]), np.arange(len(pts)))


def test_grid_cell_size_covers_radius():
    pts = make_dataset("kitti", 1000, seed=0)
    for r in [1e-4, 0.03, 1.7, 300.0]:
        g = build_grid(pts, r)
        # coverage invariant: one-ring stencil spans the radius ball — either
        # the cell is radius-sized, or that axis has a single all-covering cell
        ok = (g.cell_size >= r * (1 - 1e-6)) | (np.array(g.res) == 1)
        assert np.all(ok), (g.cell_size, g.res, r)


def test_hash_matches_numpy_and_jax():
    import jax.numpy as jnp

    coords = np.array([[0, 1, 2], [5, 5, 5], [1048575, 3, 77]], dtype=np.int64)
    h_np = hash_coords(coords, 1024)
    h_j = np.asarray(hash_coords(jnp.asarray(coords, jnp.int32), 1024))
    np.testing.assert_array_equal(h_np.astype(np.int64), h_j.astype(np.int64))


def test_stencil_shape():
    assert stencil_offsets(2).shape == (9, 2)
    assert stencil_offsets(3).shape == (27, 3)


# ------------------------------------------------------- fixed radius


def test_fixed_radius_finds_all_within_radius():
    pts = make_dataset("uniform", 800, seed=2)
    r = 0.15
    k = 40
    d, idx, found, tests = fixed_radius_knn(pts, r, k)
    d = np.asarray(d)
    p = pts.astype(np.float64)
    for q in range(0, 800, 19):
        dd = np.sqrt(((p - p[q]) ** 2).sum(-1))
        dd[q] = np.inf
        inside = np.sort(dd[dd <= r])[:k]
        got = np.sort(d[q][np.isfinite(d[q])])
        np.testing.assert_allclose(got[: len(inside)], inside, rtol=1e-5)
        assert int(np.asarray(found)[q]) == (dd <= r).sum()


def test_fixed_radius_oracle_radius_matches_brute():
    pts = make_dataset("iono", 600, seed=5)
    k = 7
    rmax = max_knn_distance(pts, k)
    d, idx, found, _ = fixed_radius_knn(pts, rmax * (1 + 1e-5), k)
    assert np.all(np.asarray(found) >= k)
    assert_knn_equal(pts, np.asarray(idx), k)


# ------------------------------------------------------------ trueknn


@pytest.mark.parametrize("name", ["uniform", "porto", "road", "iono", "kitti"])
def test_trueknn_exact_all_datasets(name):
    pts = make_dataset(name, 1200, seed=7)
    k = 5
    res = trueknn(pts, k)
    assert_knn_equal(pts, res.idxs, k)
    assert res.total_tests > 0 and res.n_rounds >= 1


def test_trueknn_large_k():
    pts = make_dataset("uniform", 500, seed=1)
    k = 22  # ~ sqrt(N), the paper's classifier-default k
    res = trueknn(pts, k)
    assert_knn_equal(pts, res.idxs, k)


def test_trueknn_does_less_work_than_brute():
    pts = make_dataset("porto", 3000, seed=11)
    res = trueknn(pts, 5)
    _, _, brute_tests = brute_knn(pts, 5)
    assert res.total_tests < brute_tests / 3


def test_trueknn_beats_oracle_fixed_radius_on_work():
    """Paper Table 2's claim: the oracle-radius baseline does many times the
    candidate distance tests TrueKNN does (skewed data)."""
    pts = make_dataset("porto", 3000, seed=13)
    k = 5
    res = trueknn(pts, k)
    rmax = max_knn_distance(pts, k)
    _, _, _, base_tests = fixed_radius_knn(pts, rmax * 1.0001, k)
    assert base_tests > 3 * res.total_tests, (base_tests, res.total_tests)


def test_trueknn_explicit_queries_no_self_exclusion():
    pts = make_dataset("uniform", 700, seed=3)
    q = make_dataset("uniform", 64, seed=99)
    res = trueknn(pts, 4, queries=q)
    p = pts.astype(np.float64)
    for i in range(64):
        dd = np.sort(np.sqrt(((p - q[i].astype(np.float64)) ** 2).sum(-1)))[:4]
        got = np.sort(
            np.sqrt(((p[res.idxs[i]] - q[i].astype(np.float64)) ** 2).sum(-1))
        )
        np.testing.assert_allclose(got, dd, rtol=1e-5, atol=1e-9)


def test_trueknn_stop_radius_leaves_tail_unresolved():
    pts = make_dataset("porto", 1500, seed=17)
    res = trueknn(pts, 5, stop_radius=1e-4)
    assert np.isinf(res.dists).any()  # tail not resolved — by design


def test_start_radius_sampling_reasonable():
    pts = make_dataset("uniform", 2000, seed=0)
    r = sample_start_radius(pts, seed=4)
    assert 0 < r < 0.1  # min 4-NN distance of a uniform 2000-pt cloud is small


def test_round_stats_monotone_radius_and_shrinking_queries():
    pts = make_dataset("road", 2000, seed=2)
    res = trueknn(pts, 5)
    radii = [r.radius for r in res.rounds]
    assert all(b > a for a, b in zip(radii, radii[1:]))
    nq = [r.n_queries for r in res.rounds]
    assert all(b <= a for a, b in zip(nq, nq[1:]))


# ------------------------------------------------------------ property


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(30, 200),
    k=st.integers(1, 8),
    d=st.sampled_from([2, 3]),
    seed=st.integers(0, 2**16),
)
def test_property_trueknn_matches_brute(n, k, d, seed):
    rng = np.random.default_rng(seed)
    # mix of cluster + uniform to exercise both grid regimes
    a = rng.normal(0, 0.01, size=(n // 2, d))
    b = rng.uniform(-1, 1, size=(n - n // 2, d))
    pts = np.concatenate([a, b]).astype(np.float32)
    res = trueknn(pts, k, seed=seed)
    assert_knn_equal(pts, res.idxs, k)


@settings(max_examples=10, deadline=None)
@given(scale=st.floats(1e-3, 1e3), shift=st.floats(-100, 100))
def test_property_scale_shift_invariant_indices(scale, shift):
    pts = make_dataset("iono", 300, seed=8)
    res_a = trueknn(pts, 3, seed=0)
    res_b = trueknn(pts * scale + shift, 3, seed=0)
    # neighbor *distances* scale; the neighbor sets must agree up to ties.
    # atol: rounding pts*scale+shift to float32 quantizes each coordinate to
    # ~eps*|shift| when |shift| dominates, so shifted-cloud distances carry
    # that absolute noise floor in addition to the scale-relative one.
    da = np.sort(res_a.dists, 1) * scale
    db = np.sort(res_b.dists, 1)
    atol = 1e-5 * abs(scale) + 4 * np.finfo(np.float32).eps * abs(shift)
    np.testing.assert_allclose(da, db, rtol=2e-3, atol=atol)
