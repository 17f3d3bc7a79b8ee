"""Paper Fig. 4: TrueKNN vs the non-RT (cuML-style) brute-force kNN, k=5."""

from repro.api import KnnSpec, build_index
from repro.core import make_dataset

from .common import cold_trueknn, emit, timed


def main():
    for name in ["road", "porto", "iono", "kitti"]:
        for n in [8_000, 16_000]:
            pts = make_dataset(name, n, seed=1)
            res, t_true = timed(lambda: cold_trueknn(pts, 5))
            oracle = build_index(pts, backend="brute")
            _, t_brute = timed(lambda: oracle.query(None, KnnSpec(5)))
            emit(
                f"vs_brute/{name}/n={n}",
                t_true * 1e6,
                f"speedup_vs_brute={t_brute/t_true:.2f}x t_brute_us={t_brute*1e6:.0f}",
            )


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
