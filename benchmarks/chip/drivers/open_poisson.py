"""Open loop: one-row requests arrive as a Poisson process at a fixed rate
and are submitted to a ``NeighborServer`` whether or not earlier ones are
done.

Traffic parameters:
  rate             requests per second;
  replace, jitter  how rows are drawn from the cloud (``deploy.query_rows``);
  server           ``NeighborServer`` keyword arguments (its defaults
                   where absent);
  warm_sizes       padded batch sizes run once at the warm start's radius;
  warm_steps, warm_step_sizes
                   lattice steps from the warm start's radius at which
                   each of ``warm_step_sizes`` is run again;
  warm_slice_s, warm_min_slices, warm_max_slices
                   warm-up traffic runs in slices of that many seconds
                   until a slice obtains no new program;
  tail_wait_s      how long past the window's close the run waits for an
                   answer before it counts as never answered;
  check_rows       answered requests the check compares.

Set-up prepares the spec, reads the index's lattice step from a search's
rounds, sends one batch of every warm size, runs the step sizes again at
the schedules ``warm_steps`` lattice steps from the warm start's (rows
from a fixed seed, the same in every run, so that every run obtains the
same programs), then runs the arrival stream until a warm
slice compiles nothing.  The window continues the same stream without a
pause: it holds ``round(rate * seconds)`` arrivals at times drawn
uniformly over the window (a Poisson process given its count), so every
seed offers the same number of requests.  Each request is timed by this
module's own clock, from when it was due to when a collector thread,
waiting on the tickets in the order they were submitted, sees its answer.
"""

from __future__ import annotations

import queue
import threading
import time

import numpy as np

from benchmarks.chip import deploy, tracing
from benchmarks.chip.harness import Window


def _hist(server) -> dict:
    """Batch-size histogram summed over the server's buckets."""
    out: dict = {}
    for b in server.stats()["buckets"].values():
        for size, count in b["batch_size_hist"].items():
            out[int(size)] = out.get(int(size), 0) + int(count)
    return out


def _sleep_until(t: float) -> None:
    delay = t - time.perf_counter()
    if delay > 0:
        with tracing.span("bench.wait"):
            time.sleep(delay)


def lattice_step(index, spec, rows) -> float:
    """The index's radius growth per round, read from the finite rounds of
    a search of ``rows``: as the index starts it, or, where that resolves
    in one round, from a start radius a thousand times smaller."""
    from repro.api import KnnSpec

    res = index.query(rows, spec)
    for start in (None, 1e-3 * res.start_radius):
        if start is not None:
            res = index.query(rows[:1], KnnSpec(spec.k, start_radius=start))
        radii = [r.radius for r in res.rounds if np.isfinite(r.radius)]
        if len(radii) >= 2:
            return radii[1] / radii[0]
    raise ValueError("no search ran two grid rounds; the lattice step "
                     "cannot be read")


class _Collector(threading.Thread):
    """Waits on each ticket in submission order and stamps when its answer
    came back; a ticket not answered by ``deadline`` counts as failed."""

    def __init__(self, n: int, deadline: float):
        super().__init__(name="bench-collector", daemon=True)
        self.tickets: queue.Queue = queue.Queue()
        self.deadline = deadline
        self.done_at = np.full(n, np.nan)
        self.results: list = [None] * n

    def run(self) -> None:
        while True:
            item = self.tickets.get()
            if item is None:
                return
            i, tk = item
            try:
                res = tk.result(
                    timeout=max(self.deadline - time.perf_counter(), 1e-3))
            except Exception:  # never answered, or answered with an error
                continue
            self.done_at[i] = time.perf_counter()
            self.results[i] = res


class Driver:
    def __init__(self, dep, traffic: dict, counter):
        from repro.api import NeighborServer

        self.dep = dep
        self.traffic = traffic
        self.counter = counter
        self.rate = float(traffic["rate"])
        self.server = NeighborServer(dep.index, **traffic.get("server", {}))
        self.warm_slices = 0

    def _rows(self, rng, m: int) -> np.ndarray:
        return deploy.query_rows(rng, self.dep.points, m, self.traffic)

    def setup(self) -> None:
        from repro.api import KnnSpec

        spec, index = self.dep.spec, self.dep.index
        self.server.prepare(spec)
        rng = deploy.seed_rng(deploy.SETUP_SEED, 1)
        sizes = [int(s) for s in self.traffic["warm_sizes"]]
        growth = lattice_step(index, spec, self._rows(rng, max(sizes)))
        for size in sizes:
            self.server.submit(self._rows(rng, size), spec).result()
        # The warm start drifts a lattice step or two either way under
        # load, and each step is another fused program per batch size
        # (the step sizes: those most batches of the window are padded to).  An
        # explicit start radius on the lattice builds the schedule a warm
        # start there would, so every batch size gets those programs here
        # and not inside the window.
        r0 = index.query(self._rows(rng, max(sizes)), spec).start_radius
        for step in self.traffic["warm_steps"]:
            shifted = KnnSpec(self.dep.k, start_radius=r0 * growth ** step)
            for size in self.traffic["warm_step_sizes"]:
                index.query(self._rows(rng, size), shifted)

    def _warm_stream(self, seed: int) -> float:
        """Warm-up arrivals until a slice obtains no new program; returns
        when the stream's next arrival is due."""
        rng = deploy.seed_rng(seed, 2)
        spec = self.dep.spec
        t = time.perf_counter()
        slice_s = float(self.traffic["warm_slice_s"])
        while True:
            before = self.counter.snapshot()[0]
            end = t + slice_s
            while t < end:
                _sleep_until(t)
                self.server.submit(self._rows(rng, 1)[0], spec)
                t += rng.exponential(1.0 / self.rate)
            self.warm_slices += 1
            quiet = self.counter.snapshot()[0] == before
            if self.warm_slices >= int(self.traffic["warm_max_slices"]) or (
                quiet and self.warm_slices >= int(
                    self.traffic["warm_min_slices"])
            ):
                return t

    def window(self, seed: int, seconds: float) -> Window:
        spec = self.dep.spec
        self.server.start()
        rng = deploy.seed_rng(seed, 3)
        n = max(1, int(round(self.rate * seconds)))
        offsets = np.sort(rng.uniform(0.0, seconds, n))
        rows = self._rows(rng, n)
        t0 = self._warm_stream(seed)
        hist0 = _hist(self.server)
        programs0 = self.counter.snapshot()[0]
        due = t0 + offsets
        sent = np.empty(n)
        collector = _Collector(
            n, t0 + seconds + float(self.traffic["tail_wait_s"]))
        collector.start()
        with tracing.span(tracing.WINDOW_SPAN):
            for i in range(n):
                _sleep_until(due[i])
                with tracing.span("bench.submit"):
                    sent[i] = time.perf_counter()
                    collector.tickets.put(
                        (i, self.server.submit(rows[i], spec)))
            _sleep_until(t0 + seconds)
        t1 = time.perf_counter()
        hist1 = _hist(self.server)
        with tracing.span("bench.result"):
            collector.tickets.put(None)
            collector.join()
        answered = np.flatnonzero(np.isfinite(collector.done_at))
        res = [collector.results[i] for i in answered]
        lat = collector.done_at[answered] - due[answered]
        compiles = self.counter.snapshot()[0] - programs0
        batches = {s: hist1.get(s, 0) - hist0.get(s, 0) for s in hist1}
        k = self.dep.k
        return Window(
            t0=t0, t1=t1, attempted=n, failed=n - len(answered),
            end_to_end={
                "p50_ms": float(np.percentile(lat, 50)) * 1e3,
                "p95_ms": float(np.percentile(lat, 95)) * 1e3,
            } if len(lat) else {},
            record={
                "latency_s": lat,
                "due_s": offsets[answered],
                "service_s": np.asarray(
                    [r.timings["service_seconds"] for r in res], np.float64),
                "late_s": sent[answered] - due[answered],
                "batch_hist": {s: c for s, c in sorted(batches.items())
                               if c},
                "batches": int(sum(batches.values())),
                "batch_rows": int(sum(s * c for s, c in batches.items())),
                "compiles": compiles,
                "warm_slices": self.warm_slices,
            },
            queries=rows[answered],
            dists=np.asarray([r.dists[0] for r in res],
                             np.float32).reshape(-1, k),
            idxs=np.asarray([r.idxs[0] for r in res],
                            np.int64).reshape(-1, k),
        )

    def close(self) -> None:
        self.server.stop()
