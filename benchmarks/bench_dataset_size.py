"""Paper Fig. 3 / Table 1: TrueKNN vs oracle-fixed-radius baseline while
varying dataset size, k = sqrt(N).  Claim validated: TrueKNN wins on every
dataset and the margin grows with N (skewed data wins biggest)."""

import numpy as np

from repro.core import make_dataset

from .common import emit, run_pair

SIZES = [4_000, 8_000, 16_000]
DATASETS = ["road", "porto", "iono", "kitti", "uniform"]


def main():
    for name in DATASETS:
        for n in SIZES:
            pts = make_dataset(name, n, seed=1)
            k = int(np.sqrt(n))
            r = run_pair(f"{name}_{n}", pts, k)
            emit(
                f"dataset_size/{name}/n={n}/k={k}",
                r["t_true"] * 1e6,
                f"speedup={r['speedup']:.2f}x test_ratio={r['test_ratio']:.2f}x "
                f"rounds={r['rounds']} t_base_us={r['t_base']*1e6:.0f}",
            )


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
