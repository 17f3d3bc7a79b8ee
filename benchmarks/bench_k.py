"""Paper Fig. 5: impact of k (k=5 vs k=sqrt(N)) at fixed dataset size.
Claim validated: TrueKNN wins in both regimes; margin is larger for small k."""

import numpy as np

from repro.core import make_dataset

from .common import emit, run_pair


def main():
    n = 10_000
    for name in ["road", "porto", "iono", "kitti"]:
        pts = make_dataset(name, n, seed=1)
        small = run_pair(f"k5_{name}", pts, 5)
        big = run_pair(f"ksqrt_{name}", pts, int(np.sqrt(n)))
        emit(
            f"impact_k/{name}/k=5",
            small["t_true"] * 1e6,
            f"speedup={small['speedup']:.2f}x test_ratio={small['test_ratio']:.1f}x",
        )
        emit(
            f"impact_k/{name}/k=100",
            big["t_true"] * 1e6,
            f"speedup={big['speedup']:.2f}x test_ratio={big['test_ratio']:.1f}x "
            f"small_k_margin_larger={small['test_ratio'] > big['test_ratio']}",
        )


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
