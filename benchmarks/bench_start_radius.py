"""Paper Fig. 7 + Sec 5.4.2: execution time is insensitive to the sampled
start radius across a 16x range; far-too-large radii hurt."""

from repro.core import make_dataset, sample_start_radius

from .common import cold_trueknn, emit, timed


def main():
    pts = make_dataset("porto", 20_000, seed=1)
    r0 = sample_start_radius(pts, seed=0)
    times = {}
    for mult in [0.25, 0.5, 1.0, 2.0, 4.0]:
        res, t = timed(lambda m=mult: cold_trueknn(pts, 5, start_radius=r0 * m))
        times[mult] = t
        emit(
            f"start_radius/x{mult}",
            t * 1e6,
            f"radius={r0*mult:.2e} rounds={res.n_rounds} tests={res.total_tests}",
        )
    spread = max(times.values()) / min(times.values())
    emit("start_radius/insensitive_within", 0.0, f"max_over_min={spread:.2f}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
