"""Paper Fig. 6a/6b: per-round time and surviving query count on the road
dataset.  Claim validated: queries drain geometrically while late (large
radius) rounds with a handful of outlier queries still cost real time."""

from repro.core import make_dataset

from .common import cold_trueknn, emit, timed


def main():
    pts = make_dataset("road", 20_000, seed=1)
    res, _ = timed(lambda: cold_trueknn(pts, 5))
    for r in res.rounds:
        emit(
            f"rounds/road/round={r.round_idx}",
            r.seconds * 1e6,
            f"radius={r.radius:.2e} queries={r.n_queries} "
            f"resolved={r.n_resolved} tests={r.n_tests}",
        )
    nq = [r.n_queries for r in res.rounds]
    emit("rounds/drain_monotone", 0.0, f"monotone={all(b <= a for a, b in zip(nq, nq[1:]))}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
