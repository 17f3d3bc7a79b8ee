"""Tests for the unified NeighborIndex API: registry round-trips against the
brute oracle, grid-cache + warm-start serving behavior, radius bookkeeping,
the clamp guard, external-query and stop_radius tail semantics."""

import numpy as np
import pytest

from repro.api import (
    KNNResult,
    NeighborIndex,
    available_backends,
    build_index,
    get_backend,
    register_backend,
)
from repro.core import brute_knn, make_dataset, max_knn_distance


def _dists_of(pts, idxs, q):
    """Float64 distances of returned neighbor indices (tie-insensitive)."""
    p = pts.astype(np.float64)
    return np.sort(
        np.sqrt(((p[idxs] - q.astype(np.float64)[:, None, :]) ** 2).sum(-1)), 1
    )


def _assert_matches_brute(pts, res, queries, k):
    """queries=None compares in self-query mode (self-excluded)."""
    bd, bi, _ = brute_knn(pts, k, queries=queries)
    if queries is None:
        queries = pts
    got = _dists_of(pts, np.clip(res.idxs, 0, len(pts) - 1), queries)
    want = _dists_of(pts, np.clip(np.asarray(bi), 0, len(pts) - 1), queries)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-9)
    np.testing.assert_allclose(
        np.sort(res.dists, 1), np.sort(np.asarray(bd), 1), rtol=1e-4, atol=1e-6
    )


# ------------------------------------------------------------- registry


def test_builtin_backends_registered():
    assert {"brute", "fixed_radius", "trueknn", "distributed"} <= set(
        available_backends()
    )


def test_unknown_backend_raises():
    with pytest.raises(ValueError, match="unknown neighbor-search backend"):
        build_index(np.zeros((10, 2), np.float32), backend="nope")


def test_register_backend_plugs_into_build_index():
    base = get_backend("brute")

    @register_backend("test_shadow")
    class ShadowIndex(base):
        pass

    try:
        idx = build_index(np.eye(4, dtype=np.float32), backend="test_shadow")
        assert isinstance(idx, NeighborIndex)
        assert idx.backend_name == "test_shadow"
        r = idx.query(None, 2)
        assert isinstance(r, KNNResult) and r.backend == "test_shadow"
    finally:
        from repro.api.registry import _BACKENDS

        _BACKENDS.pop("test_shadow", None)


# ---------------------------------------- every backend vs brute oracle


@pytest.mark.parametrize("backend", ["brute", "fixed_radius", "trueknn",
                                     "distributed"])
def test_all_backends_match_brute_2k_cloud(backend):
    pts = make_dataset("porto", 2000, seed=4)
    qs = make_dataset("porto", 128, seed=11)
    k = 6
    cfg = {}
    if backend == "fixed_radius":
        # oracle radius over the *queries*: the k-th-NN distance of the
        # worst query (external queries include outliers the dataset's own
        # maxDist doesn't cover)
        bd, _, _ = brute_knn(pts, k, queries=qs)
        cfg["radius"] = float(np.asarray(bd)[:, k - 1].max()) * (1 + 1e-5)
    index = build_index(pts, backend=backend, **cfg)
    res = index.query(qs, k)
    assert isinstance(res, KNNResult)
    assert res.backend == backend
    assert res.dists.shape == (128, k) and res.idxs.shape == (128, k)
    _assert_matches_brute(pts, res, qs, k)


@pytest.mark.parametrize("backend", ["brute", "fixed_radius", "trueknn"])
def test_self_query_excludes_self(backend):
    pts = make_dataset("uniform", 500, seed=2)
    cfg = {"radius": max_knn_distance(pts, 4) * 1.0001} if backend == "fixed_radius" else {}
    res = build_index(pts, backend=backend, **cfg).query(None, 3)
    assert not np.any(res.idxs == np.arange(500)[:, None])
    assert np.all(res.dists > 0)


# -------------------------------------------- serving: cache + warm start


def test_trueknn_index_reuses_grids_and_warm_starts():
    pts = make_dataset("kitti", 4000, seed=0)
    rng = np.random.default_rng(3)
    index = build_index(pts, backend="trueknn")
    batches = [
        pts[rng.integers(0, 4000, 128)]
        + rng.normal(scale=0.3, size=(128, 3)).astype(np.float32)
        for _ in range(3)
    ]
    r0 = index.query(batches[0], 5)
    assert r0.timings["start_radius_source"] == "sampled"
    assert r0.timings["grid_builds"] == r0.n_rounds > 0
    r1 = index.query(batches[1], 5)
    r2 = index.query(batches[2], 5)
    for r in (r1, r2):
        assert r.timings["start_radius_source"] == "warm"
        assert r.timings["grid_cache_hits"] > 0
        assert r.timings["grid_builds"] == 0  # warm batches reuse every grid
        assert r.n_rounds <= r0.n_rounds
        _assert_matches_brute(pts, r, batches[1] if r is r1 else batches[2], 5)
    s = index.stats()
    assert s["batches"] == 3
    assert s["grid_cache_hits"] >= r1.n_rounds + r2.n_rounds - 1
    assert s["cached_grids"] == s["grid_builds"]


def test_trueknn_cache_rounds_report_cache_hit_flag():
    pts = make_dataset("porto", 1500, seed=6)
    index = build_index(pts, backend="trueknn")
    index.query(None, 4)
    r = index.query(pts[:64], 4)
    assert all(rs.cache_hit for rs in r.rounds if np.isfinite(rs.radius))


def test_fixed_radius_index_caches_grid_across_batches():
    pts = make_dataset("iono", 900, seed=1)
    r = max_knn_distance(pts, 5) * 1.0001
    index = build_index(pts, backend="fixed_radius", radius=r)
    a = index.query(pts[:100], 5)
    b = index.query(pts[100:200], 5)
    assert a.timings["grid_builds"] == 1
    assert b.timings["grid_builds"] == 0 and b.timings["grid_cache_hits"] == 1


# ------------------------------------------------- radius bookkeeping


def test_final_radius_is_last_round_radius():
    pts = make_dataset("porto", 1500, seed=8)
    res = build_index(pts, backend="trueknn").query(None, 5)
    assert res.final_radius == res.rounds[-1].radius
    radii = [r.radius for r in res.rounds]
    assert radii == sorted(radii)


def test_final_radius_with_stop_radius_break():
    pts = make_dataset("porto", 1500, seed=17)
    stop = 1e-3
    res = build_index(pts, backend="trueknn").query(None, 5, stop_radius=stop)
    # every searched radius respects the stop; final_radius reports the
    # radius actually used in the last round, not a post-hoc division
    assert all(r.radius <= stop for r in res.rounds)
    if res.rounds:
        assert res.final_radius == res.rounds[-1].radius
    else:
        assert res.final_radius == res.start_radius


def test_final_radius_explicit_start_single_round():
    pts = make_dataset("uniform", 600, seed=3)
    big = max_knn_distance(pts, 4) * 2.0
    res = build_index(pts, backend="trueknn").query(None, 4, radius=big)
    assert res.n_rounds == 1
    assert res.final_radius == res.start_radius == res.rounds[0].radius == big


# ---------------------------------------------------------- clamp guard


def test_brute_equivalent_round_falls_through_to_brute(monkeypatch):
    """If rounds never resolve anything (pathological engine behavior), the
    driver must detect the single-cell brute-equivalent round and finish via
    the exact oracle instead of spinning until max_rounds."""
    from repro.api.backends import trueknn as tk

    real_round = tk.fixed_radius_round
    calls = {"n": 0}

    def never_resolves(grid, q, qid, r, k, **kw):
        calls["n"] += 1
        d2, idx, found, tests = real_round(grid, q, qid, r, k, **kw)
        return d2, idx, np.zeros_like(np.asarray(found)), tests

    monkeypatch.setattr(tk, "fixed_radius_round", never_resolves)
    pts = make_dataset("uniform", 300, seed=5)
    # fused=False: the patched per-round engine is the host loop's — the
    # fused driver never calls it (its clamp guard is covered by the
    # fused-vs-host identity matrix in test_fused_loop.py)
    res = build_index(
        pts, backend="trueknn", max_rounds=64, fused=False
    ).query(None, 3)
    # grid rounds stopped at the brute-equivalent radius, far below budget
    grid_rounds = [r for r in res.rounds if np.isfinite(r.radius)]
    assert calls["n"] == len(grid_rounds) < 30
    assert res.rounds[-1].radius == np.inf  # exact brute tail ran
    _assert_matches_brute(pts, res, None, 3)  # and self-exclusion survived


def test_max_rounds_exhaustion_still_exact():
    pts = make_dataset("porto", 1000, seed=9)
    res = build_index(
        pts, backend="trueknn", growth=1.01, max_rounds=3
    ).query(None, 4)
    assert res.rounds[-1].radius == np.inf  # brute tail engaged
    _assert_matches_brute(pts, res, None, 4)


# ------------------------------- external queries + stop_radius tail


def test_external_queries_with_stop_radius_tail_semantics():
    pts = make_dataset("porto", 2000, seed=7)
    rng = np.random.default_rng(0)
    qs = pts[rng.integers(0, 2000, 200)] + rng.normal(
        scale=0.01, size=(200, 2)
    ).astype(np.float32)
    k = 5
    stop = np.percentile(
        np.asarray(brute_knn(pts, k, queries=qs)[0])[:, k - 1], 60.0
    )
    res = build_index(pts, backend="trueknn").query(qs, k, stop_radius=stop)

    bd, _, _ = brute_knn(pts, k, queries=qs)
    bd = np.asarray(bd)
    resolved = res.found >= k
    assert resolved.any() and (~resolved).any()
    # resolved queries are exact
    np.testing.assert_allclose(
        np.sort(res.dists[resolved], 1), np.sort(bd[resolved], 1),
        rtol=1e-5, atol=1e-7,
    )
    # tail queries keep the partial (< k) neighbors they found: the finite
    # prefix is the true nearest-neighbor prefix, the rest is inf-padded
    for i in np.flatnonzero(~resolved):
        nf = int(res.found[i])
        assert nf < k
        got = np.sort(res.dists[i])
        assert np.isinf(got[nf:]).all()
        np.testing.assert_allclose(got[:nf], bd[i, :nf], rtol=1e-5, atol=1e-7)


def test_warm_index_stop_radius_still_searches():
    """A warm index whose EMA radius exceeds stop_radius must still run a
    round at the stop boundary (partial answers), not return all-inf."""
    pts = make_dataset("porto", 1500, seed=12)
    index = build_index(pts, backend="trueknn")
    index.query(None, 5)  # warms the EMA to a mid-range radius
    stop = float(index._warm_r) / 4.0
    res = index.query(pts[:100], 5, stop_radius=stop)
    assert res.n_rounds >= 1
    assert all(r.radius <= stop for r in res.rounds)
    assert np.isfinite(res.dists).any()  # partial neighbors, not empty


def test_external_queries_exact_no_self_exclusion():
    pts = make_dataset("uniform", 700, seed=3)
    q = make_dataset("uniform", 64, seed=99)
    res = build_index(pts, backend="trueknn").query(q, 4)
    _assert_matches_brute(pts, res, q, 4)
    assert res.found is not None and np.all(res.found >= 4)


# ----------------------------------------------------- shim compatibility


def test_legacy_trueknn_result_surface():
    from repro.core import TrueKNNResult, trueknn

    pts = make_dataset("uniform", 400, seed=1)
    res = trueknn(pts, 4)
    assert isinstance(res, TrueKNNResult)  # alias of KNNResult
    assert res.total_tests == res.n_tests > 0
    assert res.n_rounds == len(res.rounds) >= 1
    assert res.total_seconds > 0


def test_legacy_fixed_radius_tuple_shape():
    from repro.core import fixed_radius_knn

    pts = make_dataset("uniform", 400, seed=1)
    r = max_knn_distance(pts, 3) * 1.0001
    d, i, f, t = fixed_radius_knn(pts, r, 3)
    assert d.shape == (400, 3) and i.shape == (400, 3)
    assert np.all(np.asarray(f) >= 3) and t > 0


def test_knnlm_datastore_holds_resident_index():
    from repro.core.knnlm import build_datastore, knn_logprobs

    rng = np.random.default_rng(0)
    hid = rng.normal(size=(1200, 16)).astype(np.float32)
    tgt = rng.integers(0, 50, 1200).astype(np.int32)
    store = build_datastore(hid, tgt)
    assert isinstance(store.index, NeighborIndex)
    assert store.index.n_points == 1200
    p1 = knn_logprobs(store, hid[:32], 50, k=4)
    _ = knn_logprobs(store, hid[32:64], 50, k=4)
    assert p1.shape == (32, 50)
    np.testing.assert_allclose(p1.sum(1), 1.0, rtol=1e-4)
    # retrieval went through the resident index: grids amortized
    assert store.index.stats()["batches"] == 2
    assert store.index.stats()["grid_builds"] > 0
