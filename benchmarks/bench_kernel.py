"""Microbenchmark of the fused pairwise-distance+top-k engine vs the
unfused reference (materialized distance matrix), interpret/CPU timings plus
the analytic HBM-traffic model that motivates the fusion on TPU."""

from __future__ import annotations

import numpy as np

from repro.kernels.ops import pairwise_topk
from repro.kernels.ref import pairwise_topk_ref

from .common import emit, timed


def main():
    rng = np.random.default_rng(0)
    q = rng.normal(size=(512, 3)).astype(np.float32)
    p = rng.normal(size=(8192, 3)).astype(np.float32)
    k = 8
    import jax

    ref_j = jax.jit(lambda a, b: pairwise_topk_ref(a, b, k))
    (rd, ri, rc), t_ref = timed(lambda: jax.block_until_ready(ref_j(q, p)))
    emit("kernel/ref_unfused/512x8192", t_ref * 1e6, "materializes QxN")
    (d, i, c), t_k = timed(
        lambda: jax.block_until_ready(pairwise_topk(q, p, k))
    )
    emit(
        "kernel/pallas_interpret/512x8192",
        t_k * 1e6,
        "interpret-mode timing is NOT TPU perf; correctness+pipeline check",
    )
    # analytic HBM traffic (the fusion argument, per DESIGN.md)
    q_, n_, d_ = 512, 8192, 3
    unfused = (q_ * n_ * 4) * 2 + q_ * d_ * 4 + n_ * d_ * 4  # write+read QxN
    fused = q_ * d_ * 4 + n_ * d_ * 4 * (q_ // 256) + q_ * k * 8
    emit(
        "kernel/hbm_traffic_model",
        0.0,
        f"unfused_bytes={unfused} fused_bytes={fused} saving={unfused/fused:.1f}x",
    )
    match = np.allclose(np.asarray(d), np.asarray(rd), rtol=1e-4, atol=1e-5)
    emit("kernel/allclose_vs_ref", 0.0, f"match={match}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
