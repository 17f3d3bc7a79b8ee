#!/usr/bin/env python3
"""Sweep the offered rate of an open-loop cell to find its knee: the
highest rate at which the server's queue does not grow over the window.

    python3 benchmarks/chip/knee.py --workload kitti-serve-open \\
        --fractions 0.6,0.7,0.8,0.9,1.0,1.1 --seconds 30 --seed 7 \\
        [--out FILE]

One process, one set-up.  Set-up warms every power-of-two batch size up to
``max_batch`` at the warm start's schedule and two lattice steps either
side, whatever the cell's own warm list says, so that no rate compiles in
its window.  It then times back-to-back full batches through
``index.query``; their rows over their median time is the estimated
capacity, and each rate tried is a fraction of it (or ``--rates``, in
requests per second).  Each rate runs the cell's own warm-up stream and
then a window of ``--seconds`` through the same driver as the benchmark.

For each rate it prints, and writes to ``--out`` as JSON lines: p50 and p95
latency from the due time, the backlog's growth (requests due and not yet
answered, averaged over the window's last quarter less over its second,
per second between them: near 0 while the queue holds), the median latency
of the first and last quarter of requests, and how many batches were
padded to each power of two.  Last it prints the knee: the rate below the
lowest rate from which the backlog grows by more than ``--grow`` of the
rate at it and at every higher rate tried.  Needs a TPU, like the
benchmark.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def knee(rows: list, grow: float):
    """Highest rate tried below the lowest rate from which every rate
    grows its backlog by more than ``grow`` of itself (None if the lowest
    rate tried already grows; the highest rate if none grows)."""
    rows = sorted(rows, key=lambda r: r["rate"])
    grows = [r["backlog_growth_per_s"] > grow * r["rate"] for r in rows]
    first = len(rows)
    while first > 0 and grows[first - 1]:
        first -= 1
    return rows[first - 1]["rate"] if first > 0 else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fractions", default="",
                    help="comma-separated shares of the estimated capacity")
    ap.add_argument("--rates", default="",
                    help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--grow", type=float, default=0.02)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

    import jax
    import numpy as np

    from benchmarks.chip import deploy
    from benchmarks.chip.compiles import CompileCounter
    from benchmarks.chip.layout import Layout
    from repro.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("knee: needs a TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    layout = Layout(ROOT)
    cell = layout.cell(args.workload)
    counter = CompileCounter()
    dep = deploy.build(cell.config,
                       layout.cloud(cell.config["cloud"]["generator"]))
    max_batch = int(cell.traffic.get("server", {}).get("max_batch", 512))
    sizes = [2 ** i for i in range(max_batch.bit_length())
             if 2 ** i <= max_batch]
    traffic = dict(cell.traffic, warm_sizes=sizes, warm_step_sizes=sizes,
                   warm_steps=[1, -1, 2, -2])
    driver = layout.driver(traffic["driver"]).Driver(dep, traffic, counter)
    t = time.perf_counter()
    driver.setup()
    setup = {"setup_s": time.perf_counter() - t,
             "programs": counter.snapshot()[0]}
    rng = deploy.seed_rng(args.seed, 9)
    times = []
    for _ in range(6):
        q = deploy.query_rows(rng, dep.points, max_batch, traffic)
        s0 = time.perf_counter()
        dep.index.query(q, dep.spec)
        times.append(time.perf_counter() - s0)
    capacity = max_batch / float(np.median(times[1:]))
    setup.update(full_batch_s=times, capacity_per_s=capacity)
    print(json.dumps(setup), flush=True)

    rates = [float(r) for r in args.rates.split(",") if r]
    rates += [round(float(f) * capacity) for f in args.fractions.split(",")
              if f]
    rows = []
    for i, rate in enumerate(rates):
        driver.rate = rate
        driver.warm_slices = 0
        t = time.perf_counter()
        win = driver.window(args.seed + i, args.seconds)
        driver.server.stop()
        lat = win.record["latency_s"]
        due = win.record["due_s"]
        done = np.sort(due + lat)
        q = max(1, len(lat) // 4)
        s = args.seconds

        def backlog(lo, hi):
            t = np.linspace(lo * s, hi * s, 400)
            return float(np.mean(np.searchsorted(due, t, "right")
                                 - np.searchsorted(done, t, "right")))

        buckets: dict = {}
        for size, count in win.record["batch_hist"].items():
            b = 1 << (int(size) - 1).bit_length()
            buckets[b] = buckets.get(b, 0) + count
        row = {
            "rate": rate,
            "answered": len(lat),
            "failed": win.failed,
            "backlog_growth_per_s": (backlog(0.75, 1.0) - backlog(0.25, 0.5))
            / (0.5 * s),
            "p50_ms": win.end_to_end.get("p50_ms"),
            "p95_ms": win.end_to_end.get("p95_ms"),
            "first_quarter_p50_ms": float(np.median(lat[:q])) * 1e3,
            "last_quarter_p50_ms": float(np.median(lat[-q:])) * 1e3,
            "mean_batch_rows": (win.record["batch_rows"]
                                / max(win.record["batches"], 1)),
            "padded_batches": dict(sorted(buckets.items())),
            "compiles": win.record["compiles"],
            "warm_slices": win.record["warm_slices"],
            "wall_s": time.perf_counter() - t,
        }
        print(json.dumps(row), flush=True)
        rows.append(row)
    found = knee(rows, args.grow)
    print(json.dumps({"knee_per_s": found,
                      "cell_rate_per_s": None if found is None
                      else 0.8 * found}), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in rows)
    driver.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
