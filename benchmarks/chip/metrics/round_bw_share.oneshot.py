"""Percent of the device's peak HBM bandwidth that the grid rounds' gathers
reach: candidate slots scored in the finite-radius rounds (``n_tests``)
times the bytes of one slot, over the fused program's device time.  The
program's time includes its brute tail, and the bytes count only the
candidates' coordinates and ids, so the share is a lower bound."""

from benchmarks.chip.peaks import slot_bytes
from benchmarks.chip.readers import program


def read(record):
    p = program(record)
    peaks = record.get("peaks")
    if p is None or not peaks:
        return None
    slots = sum(s["round_tests"] for s in record["searches"])
    if slots <= 0:
        return None
    rate = slots * slot_bytes(record["d"]) / p[0]
    return 100.0 * rate / peaks["hbm_bytes_per_s"]
