"""Pallas kernel validation: interpret-mode execution vs pure-jnp oracle,
swept over shapes, dims, k, tiles, and radii (per-kernel allclose contract)."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import make_dataset
from repro.kernels.ops import pairwise_topk
from repro.kernels.ref import pairwise_topk_ref


def _check(q, p, k, radius=np.inf, query_ids=None, tq=None, tp=None):
    d2, idx, cnt = pairwise_topk(
        q, p, k, radius=radius, query_ids=query_ids, tq=tq, tp=tp
    )
    r2 = radius**2 if np.isfinite(radius) else np.inf
    rd2, ridx, rcnt = pairwise_topk_ref(q, p, k, radius2=r2, query_ids=query_ids)
    np.testing.assert_allclose(
        np.asarray(d2), np.asarray(rd2), rtol=1e-4, atol=1e-5
    )
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(rcnt))
    # indices may differ under exact distance ties; verify by distance value
    p64 = np.asarray(p, np.float64)
    q64 = np.asarray(q, np.float64)
    gi = np.asarray(idx)
    n = p.shape[0]
    for r in range(q.shape[0]):
        real = gi[r][gi[r] < n]
        got = np.sort(((p64[real] - q64[r]) ** 2).sum(-1))
        ref_real = np.asarray(ridx)[r][np.asarray(ridx)[r] < n]
        want = np.sort(((p64[ref_real] - q64[r]) ** 2).sum(-1))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-7)


@pytest.mark.parametrize("nq,np_,d,k", [
    (8, 32, 3, 1),
    (100, 700, 3, 5),
    (64, 64, 2, 8),
    (33, 257, 3, 7),     # ragged, exercises padding
    (256, 512, 8, 16),   # d > 3: beyond-paper capability
    (16, 2048, 64, 4),   # embedding-sized feature dim
    (5, 50, 1, 3),       # 1-D
])
def test_kernel_matches_ref_shapes(nq, np_, d, k):
    rng = np.random.default_rng(nq * 31 + np_ + d)
    q = rng.normal(size=(nq, d)).astype(np.float32)
    p = rng.normal(size=(np_, d)).astype(np.float32)
    _check(q, p, k)


@pytest.mark.parametrize("radius", [0.0, 0.3, 1.0, 10.0])
def test_kernel_radius_counts(radius):
    rng = np.random.default_rng(7)
    q = rng.normal(size=(50, 3)).astype(np.float32)
    p = rng.normal(size=(300, 3)).astype(np.float32)
    _check(q, p, 4, radius=radius)


@pytest.mark.parametrize("tq,tp", [(8, 128), (16, 256), (64, 128)])
def test_kernel_tile_sweep(tq, tp):
    rng = np.random.default_rng(9)
    q = rng.normal(size=(100, 3)).astype(np.float32)
    p = rng.normal(size=(500, 3)).astype(np.float32)
    _check(q, p, 5, tq=tq, tp=tp)


def test_kernel_self_exclusion():
    rng = np.random.default_rng(3)
    p = rng.normal(size=(200, 3)).astype(np.float32)
    qid = np.arange(100, dtype=np.int32)
    d2, idx, _ = pairwise_topk(p[:100], p, 3, query_ids=qid)
    assert not np.any(np.asarray(idx) == qid[:, None])
    assert np.all(np.asarray(d2) > 0)


def test_kernel_k_larger_than_points():
    rng = np.random.default_rng(4)
    q = rng.normal(size=(10, 3)).astype(np.float32)
    p = rng.normal(size=(6, 3)).astype(np.float32)
    d2, idx, cnt = pairwise_topk(q, p, 9)
    d2 = np.asarray(d2)
    idx = np.asarray(idx)
    assert np.isinf(d2[:, 6:]).all()
    assert (idx[:, 6:] == 6).all()
    assert np.isfinite(d2[:, :6]).all()


def test_kernel_dtype_inputs():
    rng = np.random.default_rng(5)
    q64 = rng.normal(size=(20, 3))
    p64 = rng.normal(size=(80, 3))
    # float64 / float16 inputs are accepted and computed in f32
    for dt in [np.float64, np.float16]:
        _check(q64.astype(dt).astype(np.float32), p64.astype(np.float32), 3)
        d2, _, _ = pairwise_topk(q64.astype(dt), p64.astype(dt), 3)
        assert np.asarray(d2).dtype == np.float32


def test_kernel_duplicate_points_ties():
    p = np.zeros((64, 3), np.float32)  # all identical — worst-case ties
    q = np.ones((4, 3), np.float32)
    d2, idx, cnt = pairwise_topk(q, p, 5, radius=10.0)
    np.testing.assert_allclose(np.asarray(d2), 3.0, rtol=1e-5)
    assert (np.asarray(cnt) == 64).all()


@settings(max_examples=25, deadline=None)
@given(
    nq=st.integers(1, 70),
    np_=st.integers(1, 300),
    d=st.integers(1, 12),
    k=st.integers(1, 10),
    seed=st.integers(0, 1 << 16),
    scale=st.floats(1e-2, 1e2),
)
def test_kernel_property(nq, np_, d, k, seed, scale):
    rng = np.random.default_rng(seed)
    q = (rng.normal(size=(nq, d)) * scale).astype(np.float32)
    p = (rng.normal(size=(np_, d)) * scale).astype(np.float32)
    d2, idx, cnt = pairwise_topk(q, p, k, radius=float(scale))
    rd2, ridx, rcnt = pairwise_topk_ref(
        q, p, k, radius2=np.float32(scale) ** 2
    )
    np.testing.assert_allclose(
        np.asarray(d2), np.asarray(rd2), rtol=1e-3, atol=1e-5 * scale**2
    )
    # counts may flicker for points exactly at the radius boundary under
    # different summation orders; allow off-by-boundary
    diff = np.abs(np.asarray(cnt).astype(int) - np.asarray(rcnt).astype(int))
    assert diff.max() <= 2


@pytest.mark.parametrize("d", [2, 3])
def test_low_d_branch_keyed_on_real_dim(d):
    """The exact per-axis form is chosen by the real feature dim, so the
    chip's 128-lane layout computes what the CPU's 8-lane layout computes,
    bit for bit, on clustered data where the matmul identity would cancel."""
    import jax.numpy as jnp

    from repro.kernels.pairwise_topk import pairwise_topk_padded

    pts = make_dataset("porto" if d == 2 else "kitti", 1024, seed=3)
    q = pts[:64] + np.float32(1e-4)

    def run(dp, n_dim):
        qp = jnp.zeros((64, dp), jnp.float32).at[:, :d].set(q)
        pp = jnp.zeros((1024, dp), jnp.float32).at[:, :d].set(pts)
        qid = jnp.full((64, 1), 1024, jnp.int32)
        r2 = jnp.asarray([[0.01]], jnp.float32)
        return pairwise_topk_padded(
            qp, qid, pp, r2, k=8, n_real=1024, tq=64, tp=512,
            interpret=True, n_dim=n_dim,
        )

    before = run(8, None)  # the CPU layout: every padded lane accumulated
    for dp in (8, 128):
        for got, want in zip(run(dp, d), before):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_interpret_mode_only_on_cpu(monkeypatch):
    """No silent interpreter on a backend that is neither CPU nor TPU."""
    import repro.kernels.ops as ops

    monkeypatch.setattr(ops.jax, "default_backend", lambda: "gpu")
    q = np.zeros((8, 3), np.float32)
    with pytest.raises(RuntimeError, match="gpu"):
        pairwise_topk(q, q, 2)


def test_compiled_kernel_refuses_k_above_max():
    import jax.numpy as jnp

    from repro.kernels.pairwise_topk import MAX_K, pairwise_topk_padded

    with pytest.raises(ValueError, match=f"k={2 * MAX_K}"):
        pairwise_topk_padded(
            jnp.zeros((64, 128)), jnp.zeros((64, 1), jnp.int32),
            jnp.zeros((512, 128)), jnp.zeros((1, 1)), k=2 * MAX_K,
            n_real=512, tq=8, tp=512, n_dim=3,
        )


def test_query_tile_shrinks_as_k_grows():
    from repro.kernels.pairwise_topk import MAX_K, query_tile

    assert [query_tile(k) for k in (1, 8, 32, 64, 128, MAX_K)] == [
        256, 256, 256, 128, 64, 32
    ]
    # a wide k still matches the oracle through the smaller tile
    rng = np.random.default_rng(11)
    q = rng.normal(size=(40, 3)).astype(np.float32)
    p = rng.normal(size=(300, 3)).astype(np.float32)
    _check(q, p, 64, radius=1.0)
