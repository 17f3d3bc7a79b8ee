"""Helpers shared by the per-layer metric readers in ``metrics/``.

A reader is ``read(record) -> float | None``: ``None`` when the run holds
nothing for it to read (no trace, no such program), never a made-up 0.
"""

from __future__ import annotations

# jit name of the fused round-loop program (``repro.core.fused_loop``,
# ``_fused_fn`` jits an inner function called ``run``); not a stable name
FUSED_PROGRAM = "jit_run"


def program(record: dict, name: str = FUSED_PROGRAM):
    """(device seconds, runs) of program ``name`` in the traced window, or
    None when the trace holds no run of it."""
    trace = record.get("trace")
    if not trace:
        return None
    p = trace["programs"].get(name)
    if not p or p["runs"] <= 0 or p["seconds"] <= 0:
        return None
    return p["seconds"], p["runs"]


def idle_share(record: dict):
    """Percent of the traced window in which the device ran nothing."""
    trace = record.get("trace")
    if not trace or not trace["devices"] or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
