#!/usr/bin/env python3
"""Bring-up run of the neighbor-search system on a TPU: one process, one run.

    python chip_smoke.py              # one chip: phases a, b, c
    python chip_smoke.py --chips 4    # the placed fabric across four chips

Each phase drives the path a user would call and checks its answers against
a plain float64 NumPy brute force over the whole cloud on sampled queries:

  a. served TrueKNN kNN: ``build_index(kitti 2^20, "trueknn")`` behind a
     ``NeighborServer``, ``prepare(KnnSpec(8))``, four closed-loop batches of
     512 queries submitted as tickets;
  b. one-shot self-kNN (the paper's all-points experiment) over a 2^20-point
     porto-like GPS cloud, ``index.query(None, KnnSpec(8))``;
  c. native range on the brute backend (the Pallas kernel compiled for the
     chip) at the warm median k-th-NN radius of phase a.

``--chips 4`` runs only the placed sharded fabric (``backend="sharded",
placement="devices"``) over 4 x 2^20 kitti points, with ``HybridSpec`` and
``KnnSpec(8)`` batches.  The times printed are of this one run, compile
included where it says so; they are not a benchmark.

The script refuses to run without a TPU, exits non-zero when any phase or
check fails, and prints as its last line one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from types import SimpleNamespace

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

N_POINTS = 1 << 20
BATCH = 512
K = 8
REF_QUERIES = 1024
# LiDAR return jitter (metres) added to sampled cloud rows to make queries
JITTER = 0.05
# float32 rounding: inputs are exact float32 values, so each distance
# carries only the relative rounding of a few float32 operations (diffs of
# nearby coordinates are exact, squares and sums add ~2^-24 each, sqrt
# halves it).  1e-5 is ~80x that; any real search error is far larger.
RTOL = 1e-5


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# -- float64 reference --------------------------------------------------------


def _over_blocks(fn, n_rows, n_points):
    """Run ``fn(row slice)`` over query blocks on a few host threads (NumPy
    releases the GIL in its array loops) and return the results in order;
    an AssertionError in any block propagates.  A block's distance matrix
    holds at most 2^24 float64 values (128 MiB), and a block needs about
    two such matrices at once."""
    from concurrent.futures import ThreadPoolExecutor

    rows = max(1, (1 << 24) // max(n_points, 1))
    blocks = [slice(s, min(s + rows, n_rows)) for s in range(0, n_rows, rows)]
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as ex:
        return list(ex.map(fn, blocks))


def _check_lists(pts, queries, offsets, ids, lo, hi, *, self_ids=None):
    """Ragged answer lists against a float64 brute force over the whole
    cloud: row r lists ``ids[offsets[r]:offsets[r + 1]]``.  Asserts that
    every row's ids are valid and distinct, that each lies within ``hi[r]``
    of the query, and that every point nearer than ``lo[r]`` is listed
    (a query's own row is skipped for self-queries).  Returns, per row,
    the points nearer than ``lo`` and within ``hi``, and the true distance
    of every listed id."""
    pts64 = np.asarray(pts, np.float64)
    q64 = np.asarray(queries, np.float64)
    n, dim = pts64.shape
    offsets, ids = np.asarray(offsets), np.asarray(ids)
    assert ((ids >= 0) & (ids < n)).all(), "an id is out of range"

    def block(sl):
        m = sl.stop - sl.start
        d2 = np.zeros((m, n))
        tmp = np.empty((m, n))
        for a in range(dim):
            np.subtract(q64[sl, a:a + 1], pts64[:, a], out=tmp)
            np.multiply(tmp, tmp, out=tmp)
            d2 += tmp
        if self_ids is not None:
            d2[np.arange(m), self_ids[sl]] = np.inf
        lo2, hi2 = lo[sl, None] ** 2, hi[sl, None] ** 2
        part = slice(offsets[sl.start], offsets[sl.stop])
        rows = np.repeat(np.arange(m), np.diff(offsets[sl.start:sl.stop + 1]))
        got = ids[part]
        assert len(np.unique(rows * n + got)) == len(got), "a repeated id"
        got_d2 = d2[rows, got]
        assert (got_d2 <= hi2[rows, 0]).all(), "an id lies beyond its bound"
        inside = d2 < lo2
        n_lo = inside.sum(1)
        n_hi = (d2 <= hi2).sum(1)
        inside[rows, got] = False
        assert not inside.any(), (
            f"{int(inside.sum())} points inside the bound are missing"
        )
        return n_lo, n_hi, np.sqrt(got_d2)

    parts = _over_blocks(block, len(q64), n)
    return tuple(np.concatenate(x) for x in zip(*parts))


def check_knn(pts, queries, dists, idxs, k, *, self_ids=None):
    """kNN answers against float64 brute force.  With t = the returned k-th
    distance: every returned distance is its id's true distance to RTOL,
    fewer than k points lie nearer than t(1 - RTOL) and all of them were
    returned, and at least k lie within t(1 + RTOL).  So t is the true k-th
    distance to RTOL and the ids are the true neighbors up to ties at it.
    Returns the number of rows checked; raises AssertionError on a miss."""
    dists = np.asarray(dists, np.float64)
    idxs = np.asarray(idxs)
    m = len(dists)
    kth = dists[:, k - 1]
    assert np.isfinite(kth).all(), "a row holds fewer than k neighbors"
    n_lo, n_hi, got = _check_lists(
        pts, queries, np.arange(m + 1) * k, idxs.ravel(),
        kth * (1 - RTOL), kth * (1 + RTOL) + 1e-12, self_ids=self_ids,
    )
    assert np.allclose(got, dists.ravel(), rtol=RTOL, atol=1e-12), (
        "a returned distance is not its id's distance"
    )
    assert (n_lo <= k - 1).all() and (n_hi >= k).all(), (
        "a k-th distance is off"
    )
    return m


def check_range(pts, queries, radius, res):
    """Range answers against float64 brute force: every returned id lies in
    the ball and every point inside it was returned, except points within
    RTOL of the radius (float32 rounding decides those), so the ball
    populations agree up to those boundary points.  Returns (rows checked,
    boundary points seen)."""
    m = len(queries)
    n_lo, n_hi, _ = _check_lists(
        pts, queries, res.offsets, res.idxs,
        np.full(m, radius * (1 - RTOL)), np.full(m, radius * (1 + RTOL)),
    )
    return m, int((n_hi - n_lo).sum())


# -- compile-time accounting --------------------------------------------------


class CompileClock:
    """Sums JAX's tracing, lowering and backend-compile durations, so a
    phase can report compile seconds apart from search seconds."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration

    def lap(self):
        s, self.seconds = self.seconds, 0.0
        return s


def _sample_rows(rng, pts, m):
    """m queries: cloud rows with LiDAR-like return jitter."""
    rows = pts[rng.integers(0, len(pts), m)]
    return np.asarray(rows + rng.normal(scale=JITTER, size=rows.shape),
                      np.float32)


# -- phases -------------------------------------------------------------------


def phase_served_knn(pts, clock, *, batches=4, batch=BATCH, k=K,
                     ref_queries=REF_QUERIES, seed=1):
    """(a) served TrueKNN kNN through NeighborServer tickets."""
    from repro.api import KnnSpec, NeighborServer, build_index

    rng = np.random.default_rng(seed)
    spec = KnnSpec(k)
    t0 = time.perf_counter()
    index = build_index(pts, backend="trueknn")
    server = NeighborServer(index, max_batch=batch)
    server.prepare(spec)
    log(f"a: index + prepare {time.perf_counter() - t0:.3f}s")
    qs, served = [], []
    counters = ("rounds", "grid_builds", "grid_cache_hits", "dispatches",
                "brute_tail_queries")
    for b in range(batches):
        q = _sample_rows(rng, pts, batch)
        before = index.stats()
        clock.lap()
        t0 = time.perf_counter()
        res = server.submit(q, spec).result()
        wall = time.perf_counter() - t0
        after = index.stats()
        c = {name: after[name] - before[name] for name in counters}
        plan = str(res.timings.get("plan"))
        log(
            f"a: batch {b} wall {wall:.3f}s compile {clock.lap():.3f}s "
            f"plan={plan} " + " ".join(f"{n}={v}" for n, v in c.items())
        )
        assert plan.startswith("fused/"), plan
        assert c["dispatches"] == 1, c
        if b >= 1:
            assert c["grid_builds"] == 0 and c["grid_cache_hits"] > 0, c
        qs.append(q)
        served.append(res)
    for q, res in zip(qs, served):
        direct = index.query(q, spec)
        assert np.array_equal(direct.dists, res.dists), "served != direct"
        assert np.array_equal(direct.idxs, res.idxs), "served != direct"
    log("a: served results equal direct index.query bit for bit")
    q_all = np.concatenate(qs)
    d_all = np.concatenate([r.dists for r in served])
    i_all = np.concatenate([r.idxs for r in served])
    sel = np.sort(rng.choice(len(q_all), min(ref_queries, len(q_all)),
                             replace=False))
    t0 = time.perf_counter()
    n_ok = check_knn(pts, q_all[sel], d_all[sel], i_all[sel], k)
    log(f"a: {n_ok} queries match the float64 reference "
        f"({time.perf_counter() - t0:.1f}s)")
    return d_all


def phase_self_knn(pts, clock, *, k=K, ref_queries=REF_QUERIES, seed=2):
    """(b) one-shot self-kNN over the whole cloud."""
    from repro.api import KnnSpec, build_index

    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    index = build_index(pts, backend="trueknn")
    clock.lap()
    t1 = time.perf_counter()
    res = index.query(None, KnnSpec(k))
    wall = time.perf_counter() - t1
    compile_s = clock.lap()
    tm = res.timings
    log(
        f"b: build {t1 - t0:.3f}s; query wall {wall:.3f}s = compile "
        f"{compile_s:.3f}s + the rest {wall - compile_s:.3f}s (grid set-up "
        f"{tm.get('grid_build_seconds', 0):.3f}s, its compiles included); "
        f"plan={tm.get('plan')} rounds={res.n_rounds}"
    )
    caps = [(f"{rs.radius:.3g}", rs.grid_cap, rs.n_queries)
            for rs in res.rounds]
    log(f"b: rounds (radius, cap, queries in) {caps}")
    sel = np.sort(rng.choice(len(pts), min(ref_queries, len(pts)),
                             replace=False))
    t0 = time.perf_counter()
    n_ok = check_knn(pts, pts[sel], res.dists[sel], res.idxs[sel], k,
                     self_ids=sel)
    log(f"b: {n_ok} queries match the float64 reference "
        f"({time.perf_counter() - t0:.1f}s)")


def phase_range(pts, radius, clock, *, batches=2, batch=BATCH, seed=3,
                require_kernel=True):
    """(c) native range on the brute backend's Pallas kernel."""
    import jax

    from repro.api import RangeSpec, build_index
    from repro.kernels.ops import pairwise_topk

    rng = np.random.default_rng(seed)
    index = build_index(pts, backend="brute")
    spec = RangeSpec(radius)
    qs, results = [], []
    for b in range(batches):
        q = _sample_rows(rng, pts, batch)
        clock.lap()
        t0 = time.perf_counter()
        res = index.query(q, spec)
        wall = time.perf_counter() - t0
        max_ball = int(res.counts.max()) if res.n_queries else 0
        log(
            f"c: batch {b} wall {wall:.3f}s compile {clock.lap():.3f}s "
            f"plan={res.timings.get('plan')} passes="
            f"{res.timings.get('count_rounds')} largest ball {max_ball}"
        )
        qs.append(q)
        results.append((res, max_ball))
    # the kernel widths the counted plan asked for: k0 = 32, then the
    # next power of two above the fullest ball when that is larger
    ks = {32}
    for _, max_ball in results:
        if max_ball > 32:
            ks.add(1 << (max_ball - 1).bit_length())
    for k in sorted(ks):
        text = jax.jit(
            lambda q, p, k=k: pairwise_topk(q, p, k, radius=radius)
        ).lower(qs[0], pts).as_text()
        mosaic = "tpu_custom_call" in text
        log(f"c: kernel at k={k} lowers to Mosaic: {mosaic}")
        if require_kernel:
            assert mosaic, f"k={k} did not lower to a Mosaic kernel"
    t0 = time.perf_counter()
    n_ok, edge = 0, 0
    for q, (res, _) in zip(qs, results):
        n, e = check_range(pts, q, radius, res)
        n_ok += n
        edge += e
    log(f"c: {n_ok} queries match the float64 reference ({edge} points "
        f"within rounding of the radius; {time.perf_counter() - t0:.1f}s)")


def phase_placed(pts, clock, *, n_devices, batch=BATCH, k=K,
                 ref_queries=REF_QUERIES, seed=4):
    """Placed sharded fabric: shard blocks pinned across the devices, one
    fused dispatch per shared-cut round."""
    from repro.api import HybridSpec, KnnSpec, build_index, warm_default_radius

    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    index = build_index(pts, backend="sharded", n_shards=8,
                        placement="devices")
    log(f"placed: build {time.perf_counter() - t0:.3f}s")
    checks = []
    for name in ("knn", "hybrid", "knn"):
        q = _sample_rows(rng, pts, batch)
        if name == "hybrid":
            spec = HybridSpec(k, warm_default_radius(checks[0][2], index))
        else:
            spec = KnnSpec(k)
        clock.lap()
        t0 = time.perf_counter()
        res = index.query(q, spec)
        wall = time.perf_counter() - t0
        plan = str(res.timings.get("plan"))
        log(
            f"placed: {spec} wall {wall:.3f}s compile {clock.lap():.3f}s "
            f"plan={plan} rounds={res.n_rounds} "
            f"fused_dispatches={res.timings.get('fused_dispatches')}"
        )
        # the fused round loop runs every shared-cut round in one dispatch
        assert 1 <= res.timings.get("fused_dispatches", 0) <= max(
            res.n_rounds, 1
        ), "a shared-cut round took more than one dispatch"
        checks.append((q, spec, res.dists, res.idxs))
    occ = index.stats()["placement"]["device_occupancy"]
    log(f"placed: device occupancy {occ}")
    assert len(occ) == n_devices and all(v > 0 for v in occ), occ
    t0 = time.perf_counter()
    n_ok = 0
    # kNN batches share the sample; every hybrid row is checked on top
    per = max(1, ref_queries // (len(checks) - 1))
    for q, spec, d, i in checks:
        if isinstance(spec, HybridSpec):
            # the kNN answer cut at the radius: rows whose k-th neighbor
            # lies inside it are plain kNN rows; the others hold exactly
            # the ball's members
            full = np.isfinite(d[:, -1])
            n_ok += check_knn(pts, q[full], d[full], i[full], k)
            part = np.flatnonzero(~full)
            n_ok += check_range(pts, q[part], spec.radius, _csr(d[part],
                                                               i[part]))[0]
        else:
            sel = np.sort(rng.choice(len(q), min(per, len(q)),
                                     replace=False))
            n_ok += check_knn(pts, q[sel], d[sel], i[sel], k)
    log(f"placed: {n_ok} queries match the float64 reference "
        f"({time.perf_counter() - t0:.1f}s)")


def _csr(dists, idxs):
    """Padded partial kNN rows (inf past the found ones) as the CSR
    ``offsets``/``idxs`` pair that ``check_range`` reads."""
    found = np.isfinite(dists)
    offsets = np.concatenate([[0], np.cumsum(found.sum(1))])
    return SimpleNamespace(offsets=offsets, idxs=idxs[found])


# -- entry point --------------------------------------------------------------


def run_phase(name, fn, *args, **kw):
    """Run one phase and log its wall time and the host's peak RSS."""
    import resource

    t0 = time.perf_counter()
    out = fn(*args, **kw)
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    log(f"{name}: done in {time.perf_counter() - t0:.1f}s; host peak RSS "
        f"{rss} bytes")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the placed fabric across four chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.compile_cache import enable_compile_cache
    from repro.core import make_dataset

    log(f"compile cache: {enable_compile_cache()}")
    log(f"device {dev.device_kind} x{len(devices)}; one run, not a benchmark")
    clock = CompileClock()
    t_all = time.perf_counter()
    if args.chips == 4:
        pts = make_dataset("kitti", 4 * N_POINTS, seed=0)
        run_phase("placed", phase_placed, pts, clock, n_devices=len(devices))
    else:
        from repro.api import warm_default_radius

        kitti = make_dataset("kitti", N_POINTS, seed=0)
        warm = run_phase("a", phase_served_knn, kitti, clock)
        run_phase("b", phase_self_knn, make_dataset("porto", N_POINTS, seed=0),
                  clock)
        radius = warm_default_radius(warm)
        log(f"c: radius {radius:.6f} (warm median k-th-NN distance)")
        run_phase("c", phase_range, kitti, radius, clock)
    peak = dev.memory_stats().get("peak_bytes_in_use")
    log(f"total {time.perf_counter() - t_all:.1f}s; peak HBM "
        f"{peak} bytes on device 0")
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
