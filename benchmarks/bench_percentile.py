"""Paper Fig. 8/9 + Table 3: 99th-percentile (outlier-free) thought
experiment and the uniform dataset.  Claims validated: (a) TrueKNN beats even
the 99th-pct oracle baseline on work; (b) uniform data is the worst case yet
still wins; (c) full TrueKNN can beat the 99th-pct baseline outright."""

import numpy as np

from repro.api import HybridSpec, build_index
from repro.core import make_dataset, percentile_knn_distance

from .common import cold_trueknn, emit, timed


def main():
    for name in ["porto", "iono", "kitti", "uniform"]:
        n = 8_000
        pts = make_dataset(name, n, seed=1)
        k = int(np.sqrt(n))
        r99 = percentile_knn_distance(pts, k, 99.0)
        # 99th-pct-terminated TrueKNN vs 99th-pct-radius baseline
        res99, t99 = timed(lambda: cold_trueknn(pts, k, stop_radius=r99))
        base99 = build_index(pts, backend="fixed_radius")
        b_res, t_b99 = timed(lambda: base99.query(None, HybridSpec(k, r99)))
        btests = b_res.n_tests
        # full (unbounded) TrueKNN
        resf, tf = timed(lambda: cold_trueknn(pts, k))
        emit(
            f"pct99/{name}",
            t99 * 1e6,
            f"speedup_vs_pct99_base={t_b99/t99:.2f}x "
            f"test_ratio={btests/max(res99.total_tests,1):.2f}x "
            f"full_trueknn_vs_pct99_base={t_b99/tf:.2f}x",
        )


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
