#!/usr/bin/env python3
"""Read the control of a cell at its own size: the reference computed in
bfloat16 (``reference.bf16_knn``), one precision below the
configuration's float32, put in the program's place.  Host NumPy only; it
needs no accelerator and reads the same numbers on any machine.

    python3 benchmarks/chip/control.py --workload kitti-frame-closed \\
        --seeds 1,2,3 [--threads 4]

For each seed it draws as many query rows as the cell's check compares,
as the cell's traffic mix draws them, from the cell's whole cloud, answers
them with the control, and prints one JSON line with the compared numbers,
the cell's limits and whether the control reads correct (it must not).
The smallest reading of each number over the seeds is that number's upper
reading (PERF.md, section 2).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--threads", type=int, default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT]

    from benchmarks.chip import deploy, reference
    from benchmarks.chip.harness import CHECK_STREAM
    from benchmarks.chip.layout import Layout

    layout = Layout(ROOT)
    cell = layout.cell(args.workload)
    config = cell.config
    n, d, k = int(config["n"]), int(config["d"]), int(config["spec"]["k"])
    pts = layout.cloud(config["cloud"]["generator"]).make(
        n, d, int(config["data_seed"]), config["cloud"])
    limits = {name: float(v) for name, v in cell.limits.items()}
    for seed in (int(s) for s in args.seeds.split(",") if s):
        rng = deploy.seed_rng(seed, CHECK_STREAM)
        q = deploy.query_rows(rng, pts, int(cell.traffic["check_rows"]),
                              cell.traffic)
        t = time.perf_counter()
        dists, idxs = reference.bf16_knn(pts, q, k, threads=args.threads)
        numbers = reference.compare(pts, q, dists, idxs, k)
        numbers["unanswered"] = 0
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "seconds": time.perf_counter() - t,
            "correct": reference.judge(numbers, limits),
            "check": {name: {"value": numbers[name], "limit": limit}
                      for name, limit in limits.items()},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
