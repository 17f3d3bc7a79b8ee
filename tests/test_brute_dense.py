"""The brute backend's dense engine at descriptor width (d=128): exact
10-NN against a float64 NumPy brute force, float32 cross terms on the MXU
branch, its spans and its pair counter."""

import numpy as np
import pytest

from repro import trace
from repro.api import KnnSpec, build_index

N, D, Q, K = 4096, 128, 256, 10
JITTER = 16.0

rng = np.random.default_rng(15)
# integer coordinates 0..255, as uint8 image descriptors store them
PTS = rng.integers(0, 256, (N, D)).astype(np.float32)
SRC = rng.choice(N, Q, replace=False)
QS = (PTS[SRC] + rng.normal(scale=JITTER, size=(Q, D))).astype(np.float32)

# Tolerance, relative to each row's true k-th distance.  The engine forms
# d^2 = |q|^2 + |p|^2 - 2 q.p in float32: each term is ~2.8e6 here, so
# rounding leaves an absolute error of a few float32 ulps of that (~1),
# and a distance error of that over 2 d, at most ~1e-5 of the k-th
# distance for the nearest neighbour (d ~ 181, the jitter's length).
# Cross terms from bfloat16 operands (2^-9 per query coordinate) would err
# by ~1e3 in d^2, ~2e-3 of the k-th distance: 20x above the tolerance.
TOL = 1e-4


def _reference(pts, qs, k):
    """float64 squared distances and the true k nearest ids, nearest
    first, of every query."""
    p = pts.astype(np.float64)
    q = qs.astype(np.float64)
    d2 = np.zeros((len(q), len(p)))
    for a in range(p.shape[1]):  # one axis at a time: (Q, N) memory only
        d2 += (q[:, a:a + 1] - p[:, a]) ** 2
    order = np.argsort(d2, axis=1, kind="stable")
    return d2, order[:, :k], np.sqrt(np.take_along_axis(d2, order, 1))


def test_brute_backend_matches_float64_at_d128():
    index = build_index(PTS, backend="brute")
    res = index.query(QS, KnnSpec(K))
    d2, want_ids, sorted_d = _reference(PTS, QS, K)
    kth = sorted_d[:, K - 1]
    id_d = np.sqrt(np.take_along_axis(d2, res.idxs.astype(np.int64), 1))
    assert res.dists.shape == (Q, K) and res.idxs.shape == (Q, K)
    # returned distances are their ids' true distances, nearest first
    assert np.max(np.abs(res.dists - id_d) / kth[:, None]) < TOL
    assert np.max(np.abs(res.dists[:, -1] - kth) / kth) < TOL
    assert (np.diff(res.dists, axis=1) >= 0).all()
    # no returned id lies beyond the true k-th distance
    assert np.max((id_d.max(1) - kth) / kth) < TOL
    # where the k-th and (k+1)-th true distances stand apart by more than
    # the tolerance, the id sets are the true ones
    clear = (sorted_d[:, K] - kth) / kth > 2 * TOL
    assert clear.mean() > 0.75
    for got, want in zip(res.idxs[clear], want_ids[clear]):
        assert set(got.tolist()) == set(want.tolist())
    # the jittered source row is each query's nearest neighbour
    assert (res.idxs[:, 0] == SRC).all()


def test_pair_tests_count_query_point_pairs():
    index = build_index(PTS[:512], backend="brute")
    assert index.stats()["pair_tests"] == 0
    index.query(QS[:100], KnnSpec(K))
    index.query(QS[:7], KnnSpec(K))
    assert index.stats()["pair_tests"] == 107 * 512


def test_answers_identical_with_spans_on():
    index = build_index(PTS[:1024], backend="brute")
    off = index.query(QS[:64], KnnSpec(K))
    trace.enable(True)
    try:
        on = index.query(QS[:64], KnnSpec(K))
    finally:
        trace.enable(False)
    np.testing.assert_array_equal(off.dists, on.dists)
    np.testing.assert_array_equal(off.idxs, on.idxs)


def _lowered(d):
    import jax
    import jax.numpy as jnp

    from repro.core.brute import _brute_impl

    return _brute_impl.lower(
        jax.ShapeDtypeStruct((1024, d), jnp.float32),
        jax.ShapeDtypeStruct((512, d), jnp.float32),
        jax.ShapeDtypeStruct((512,), jnp.int32),
        k=K, chunk=512, exclude_self=False, metric="l2",
    ).as_text(debug_info=True)


def test_d128_cross_term_asks_for_highest_precision():
    """On a TPU a float32 dot at default precision rounds its operands to
    bfloat16; the engine's MXU branch asks for float32 operands."""
    text = _lowered(D)
    dots = [ln for ln in text.splitlines() if "stablehlo.dot_general" in ln]
    assert len(dots) == 1
    assert "precision = [HIGHEST, HIGHEST]" in dots[0]


def test_d3_diff_branch_has_no_dot():
    assert "dot_general" not in _lowered(3)


@pytest.mark.parametrize("d", [3, D])
def test_engine_names_its_distance_and_selection_scopes(d):
    text = _lowered(d)
    for scope in ("brute.dist", "brute.select"):
        assert f"{scope}/" in text, scope


@pytest.mark.parametrize("backend,cfg", [
    ("trueknn", {}),
    ("fixed_radius", {"radius": 1.0}),
    ("sharded", {"n_shards": 2}),
], ids=["trueknn", "fixed_radius", "sharded"])
def test_grid_engines_refuse_d_above_3_at_build(backend, cfg):
    """A grid hashes at most three axes; building one over descriptors
    fails with a message that names the dense engine, before any grid."""
    with pytest.raises(ValueError, match='backend="brute"'):
        build_index(PTS[:256], backend=backend, **cfg)
