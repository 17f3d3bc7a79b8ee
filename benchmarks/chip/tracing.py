"""Profiler capture of the measured window and its reduction to numbers.

``Capture`` wraps ``jax.profiler`` around the window and turns the written
``.xplane.pb`` into a compact record (``load``):

  window_ns   length of the harness's ``bench.window`` span;
  host        the harness's own spans (names starting ``bench.``), as
              [name, start_ns, dur_ns, thread], times from the window start;
  devices     per accelerator plane, its "XLA Ops" and "XLA Modules"
              events as [name, start_ns, dur_ns], clipped to the window.

``reduce`` computes from that record the device's busy time (the union of
its op intervals), device time per program, the costliest ops, and the
longest idle gaps, each labelled with the innermost harness span open at
the gap's middle.  The reduction reads nothing but the compact record, so
a recorded trace (``testdata/``) checks it on any machine.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import tempfile

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

_active = False


def span(name: str):
    """A host span in the profiler's trace while a capture is running, and
    nothing otherwise."""
    if not _active:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


class Capture:
    """``with Capture() as cap: ...`` traces the block; ``cap.record`` then
    holds the compact record of the ``bench.window`` span inside it."""

    def __init__(self):
        self.record = None
        self._tmp = None

    def __enter__(self):
        import jax

        global _active
        self._tmp = tempfile.TemporaryDirectory(prefix="bench_trace_")
        # the device's ops, and of the host only the harness's own spans:
        # the default options also trace every Python call and the
        # runtime's own host events, which slowed the served cell's host
        # enough to overload it
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self._tmp.name, profiler_options=opts)
        _active = True
        return self

    def __exit__(self, *exc):
        import jax

        global _active
        _active = False
        jax.profiler.stop_trace()
        try:
            if exc[0] is None:
                paths = glob.glob(os.path.join(
                    self._tmp.name, "**", "*.xplane.pb"), recursive=True)
                if not paths:
                    raise RuntimeError("the profiler wrote no .xplane.pb")
                self.record = load(max(paths, key=os.path.getmtime))
        finally:
            self._tmp.cleanup()
        return False


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[8]{0} fusion(...)`` -> ``fusion.12``: an op
    event's HLO instruction name without its text."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith("/device:CPU")


def load(path: str) -> dict:
    """Compact record of the ``bench.window`` span of one xplane file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host, devices = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns), line.name])
        elif _is_device_plane(plane.name):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    short = op_name if line.name == OPS_LINE else str
                    lines[line.name] = [
                        [short(ev.name), int(ev.start_ns), int(ev.duration_ns)]
                        for ev in line.events
                    ]
            if lines:
                devices[plane.name] = {
                    "ops": lines.get(OPS_LINE, []),
                    "modules": lines.get(MODULES_LINE, []),
                }
    windows = [h for h in host if h[0] == WINDOW_SPAN]
    if not windows:
        raise RuntimeError(f"the trace holds no {WINDOW_SPAN!r} span")
    _, w0, wlen, _ = windows[0]

    def clip(evs):
        out = []
        for name, s, dur in evs:
            a, b = max(s - w0, 0), min(s + dur - w0, wlen)
            if b > a:
                out.append([name, a, b - a])
        return out

    return {
        "window_ns": wlen,
        "host": [[n, s - w0, d, t] for n, s, d, t in host
                 if s - w0 < wlen and s + d - w0 > 0],
        "devices": {
            name: {"ops": clip(v["ops"]), "modules": clip(v["modules"])}
            for name, v in devices.items()
        },
    }


def _union(intervals):
    """Sorted disjoint [start, end) intervals covering ``intervals``."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def program_name(module_event_name: str) -> str:
    """``jit_run(123)`` -> ``jit_run``: a module's name without its id."""
    return re.sub(r"\(\d+\)$", "", module_event_name).strip()


def _label(host, t: int) -> str:
    """The innermost harness span (other than the window) open at t."""
    best = None
    for name, s, d, _ in host:
        if name != WINDOW_SPAN and s <= t < s + d:
            if best is None or d < best[1]:
                best = (name, d)
    return best[0] if best else WINDOW_SPAN


def reduce(record: dict, top: int = 10) -> dict:
    """Busy and window seconds (busy averaged over the device planes that
    ran anything), device seconds and runs per program, the ``top``
    costliest ops, and the ``top`` longest idle gaps of the first device
    with the harness span open in each."""
    wlen = record["window_ns"]
    busy, programs, ops, gaps = [], {}, {}, []
    for _, dev in sorted(record["devices"].items()):
        if not dev["ops"]:
            continue
        merged = _union([(s, s + d) for _, s, d in dev["ops"]])
        busy.append(sum(e - s for s, e in merged))
        for name, _, d in dev["ops"]:
            ops[name] = ops.get(name, 0) + d
        for name, _, d in dev["modules"]:
            p = programs.setdefault(program_name(name), [0, 0])
            p[0] += d
            p[1] += 1
        if len(busy) == 1:
            edges = [0] + [x for iv in merged for x in iv] + [wlen]
            for a, b in zip(edges[::2], edges[1::2]):
                if b > a:
                    gaps.append((b - a, _label(record["host"], (a + b) // 2)))
    gaps.sort(key=lambda g: -g[0])
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    n_dev = max(len(busy), 1)
    return {
        "window_s": wlen / 1e9,
        "busy_s": sum(busy) / n_dev / 1e9,
        "devices": len(busy),
        "programs": {k: {"seconds": v[0] / 1e9 / n_dev,
                         "runs": v[1] // n_dev}
                     for k, v in programs.items()},
        "device_ops": [[k, v / 1e9 / n_dev] for k, v in top_ops],
        "idle_gaps": [[label, g / 1e9] for g, label in gaps[:top]],
    }
