#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator this process is started on.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json`` at the checkout's root.  The run
builds the cell's deployment, warms up every shape its traffic uses (set-up),
measures for ``--seconds``, checks a seeded sample of the window's answers
against a float64 brute force, and prints one JSON line last on stdout:
end-to-end metrics with ``--trace 0``, per-layer metrics (with the device's
busy time and a breakdown from the profiler's trace) with ``--trace 1``.
Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits non-zero.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    for path in (os.path.join(ROOT, "src"), ROOT):
        if path not in sys.path:
            sys.path.insert(0, path)
    try:
        from benchmarks.chip.harness import run_cell
        from benchmarks.chip.layout import Layout
        import repro  # noqa: F401  the system under test
    except ImportError as e:
        print(f"bench: cannot import the benchmark or the system: {e}",
              file=sys.stderr)
        return 2
    return run_cell(Layout(ROOT), args.workload, seed=args.seed,
                    seconds=args.seconds, trace=bool(args.trace),
                    t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
