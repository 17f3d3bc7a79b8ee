"""One run of one cell: set up, warm up, measure, check, report.

The driver named by the cell's traffic mix does the set-up and the measured
window; this module checks the device, times set-up, captures the trace of
the window when asked, compares a seeded sample of the window's answers with
the float64 reference, and prints the result line.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import sys
import time

import numpy as np

from benchmarks.chip import deploy, reference, tracing
from benchmarks.chip.compiles import CompileCounter
from benchmarks.chip.peaks import peaks_for

# the stream of a run's seed that draws the rows the check compares
CHECK_STREAM = 99


@dataclasses.dataclass
class Window:
    """What a driver hands back from its measured window."""

    t0: float  # perf_counter at the window's start
    t1: float  # perf_counter at the window's end
    attempted: int  # requests (or query rows) due in the window
    failed: int  # of those: raised, or never answered
    end_to_end: dict  # metric name -> value
    record: dict  # what the per-layer readers read
    queries: np.ndarray  # (m, d) every answered query row of the window
    dists: np.ndarray  # (m, k) returned distances
    idxs: np.ndarray  # (m, k) returned ids


def _merge(base: dict, extra: dict) -> dict:
    out = dict(base)
    for key, val in (extra or {}).items():
        if isinstance(val, dict) and isinstance(out.get(key), dict):
            out[key] = _merge(out[key], val)
        else:
            out[key] = val
    return out


def check_sample(win: Window, dep, traffic: dict, seed: int) -> dict:
    """The compared numbers over a seeded sample of the window's answers,
    plus the count of requests that never came back."""
    m = len(win.queries)
    want = min(int(traffic["check_rows"]), m)
    rng = deploy.seed_rng(seed, CHECK_STREAM)
    sel = np.sort(rng.choice(m, want, replace=False)) if want else []
    with tracing.span("bench.check"):
        numbers = reference.compare(dep.points, win.queries[sel],
                                    win.dists[sel], win.idxs[sel], dep.k)
    numbers["unanswered"] = int(win.failed)
    return numbers


def host_rss() -> dict:
    """This process's resident and peak resident host memory, in bytes."""
    import resource

    with open("/proc/self/statm") as f:
        pages = int(f.read().split()[1])
    return {
        "rss_bytes": pages * resource.getpagesize(),
        "peak_rss_bytes": resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss * 1024,
    }


def _device_info(devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(max(peaks)),
    }


def run_cell(layout, workload: str, *, seed: int, seconds: float,
             trace: bool, t_start: float, require_tpu: bool = True,
             compile_cache: bool = True, overrides: dict = None,
             out=None, err=None) -> int:
    """Run ``workload`` once and print its result line; returns the exit
    code.  ``overrides`` (tests only) replaces configuration or traffic
    values, as ``{"config": {...}, "traffic": {...}}``."""
    import jax

    out = out or sys.stdout
    err = err or sys.stderr
    overrides = overrides or {}
    cell = layout.cell(workload)
    config = _merge(cell.config, overrides.get("config"))
    traffic = _merge(cell.traffic, overrides.get("traffic"))

    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            print(f"bench: needs a TPU, JAX found {devices[0].platform!r}",
                  file=err)
            return 2
        if len(devices) < cell.chips:
            print(f"bench: cell {workload} needs {cell.chips} chips, JAX "
                  f"sees {len(devices)}", file=err)
            return 2
        peaks = peaks_for(devices[0].device_kind)
    else:
        peaks = None
    if compile_cache:
        from repro.compile_cache import enable_compile_cache

        enable_compile_cache()
        # every program goes to the persistent cache, however fast it
        # compiled, so a second run of the cell compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = CompileCounter()
    rss = {"devices": host_rss()["rss_bytes"]}

    dep = deploy.build(config, layout.cloud(config["cloud"]["generator"]))
    driver = layout.driver(traffic["driver"]).Driver(dep, traffic, counter)
    rss["index"] = host_rss()["rss_bytes"]
    driver.setup()
    rss["setup"] = host_rss()["rss_bytes"]
    capture = tracing.Capture() if trace else contextlib.nullcontext()
    with capture:
        win = driver.window(seed, seconds)
    driver.close()
    rss["window"] = host_rss()["rss_bytes"]
    setup_s = win.t0 - t_start
    device = _device_info(devices)
    programs, loads, compile_s = counter.snapshot()
    # the reference runs on the host once the program's state is freed
    del driver
    dep.index = None

    t_check = time.perf_counter()
    numbers = check_sample(win, dep, traffic, seed)
    check_s = time.perf_counter() - t_check
    rss["check"] = host_rss()["rss_bytes"]
    limits = {name: float(v) for name, v in cell.limits.items()}
    correct = reference.judge(numbers, limits)

    result = {
        "correct": bool(correct),
        "attempted": int(win.attempted),
        "failed": int(win.failed),
        "device": device,
    }
    if trace:
        summary = tracing.reduce(capture.record)
        record = dict(win.record, trace=summary, peaks=peaks, d=dep.points
                      .shape[1], window_s=win.t1 - win.t0)
        metrics = {}
        for m in cell.per_layer:
            value = layout.metric_reader(m["name"]).read(record)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["metrics"] = metrics
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    else:
        values = dict(win.end_to_end, setup_s=setup_s)
        result["metrics"] = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in cell.end_to_end
        }
    result["setup"] = {"seconds": setup_s, "programs": programs,
                       "cache_loads": loads, "compile_s": compile_s,
                       "window_programs": win.record["compiles"],
                       "check_s": check_s, "rss_bytes_after": rss,
                       "peak_rss_bytes": host_rss()["peak_rss_bytes"]}
    if "batch_hist" in win.record:
        result["window"] = {"batch_hist": win.record["batch_hist"]}
    result["check"] = {
        name: {"value": numbers[name], "limit": limit}
        for name, limit in limits.items()
    }
    for name, c in result["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=err)
    print(f"check rows_checked {numbers['rows_checked']} correct "
          f"{str(correct).lower()}", file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
    return 0
