"""Closed loop, one client: ``index.query(rows, spec)`` back to back.

Traffic parameters:
  rows_per_search  query rows in each search;
  replace, jitter  how rows are drawn from the cloud (``deploy.query_rows``);
  warm_max         most warm-up searches before the window;
  check_rows       answered rows the check compares.

Set-up runs searches until one obtains no new program.  Its queries come
from a fixed seed, the same in every run, so that every run obtains the
same programs and the second run of a cell finds them all in the cache.
The window runs searches until ``seconds`` have passed and ends with the
last one, so ``queries_per_s`` covers all the work and all the time of the
window.
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmarks.chip import deploy, tracing
from benchmarks.chip.harness import Window

COUNTERS = ("brute_tail_queries", "rounds", "dispatches", "grid_builds",
            "grid_cache_hits", "queries_served")


def _counters(index) -> dict:
    s = index.stats()
    return {c: int(s.get(c, 0)) for c in COUNTERS}


class Driver:
    def __init__(self, dep, traffic: dict, counter):
        self.dep = dep
        self.traffic = traffic
        self.counter = counter
        self.warm_searches = 0

    def _rows(self, rng) -> np.ndarray:
        return deploy.query_rows(rng, self.dep.points,
                                 int(self.traffic["rows_per_search"]),
                                 self.traffic)

    def setup(self) -> None:
        rng = deploy.seed_rng(deploy.SETUP_SEED, 1)
        for i in range(int(self.traffic["warm_max"])):
            before = self.counter.snapshot()[0]
            self.dep.index.query(self._rows(rng), self.dep.spec)
            self.warm_searches = i + 1
            if i >= 1 and self.counter.snapshot()[0] == before:
                break

    def window(self, seed: int, seconds: float) -> Window:
        index, spec = self.dep.index, self.dep.spec
        rng = deploy.seed_rng(seed, 2)
        before = _counters(index)
        programs0 = self.counter.snapshot()[0]
        searches, qs, ds, ids = [], [], [], []
        with tracing.span(tracing.WINDOW_SPAN):
            t0 = time.perf_counter()
            while True:
                with tracing.span("bench.generate"):
                    q = self._rows(rng)
                with tracing.span("bench.query"):
                    s0 = time.perf_counter()
                    res = index.query(q, spec)
                    s1 = time.perf_counter()
                finite = [r for r in res.rounds if math.isfinite(r.radius)]
                searches.append({
                    "rows": len(q),
                    "seconds": s1 - s0,
                    "round_tests": int(sum(r.n_tests for r in finite)),
                    "rounds": len(finite),
                })
                qs.append(q)
                ds.append(res.dists)
                ids.append(res.idxs)
                if s1 - t0 >= seconds:
                    break
            t1 = time.perf_counter()
        after = _counters(index)
        rows = sum(s["rows"] for s in searches)
        return Window(
            t0=t0, t1=t1, attempted=rows, failed=0,
            end_to_end={"queries_per_s": rows / (t1 - t0)},
            record={
                "searches": searches,
                "rows": rows,
                "counters": {c: after[c] - before[c] for c in COUNTERS},
                "compiles": self.counter.snapshot()[0] - programs0,
                "warm_searches": self.warm_searches,
            },
            queries=np.concatenate(qs),
            dists=np.concatenate(ds),
            idxs=np.concatenate(ids),
        )

    def close(self) -> None:
        pass
