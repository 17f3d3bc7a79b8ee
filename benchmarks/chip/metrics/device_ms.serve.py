"""Device milliseconds of the fused round-loop program per served batch,
from the profiler's trace."""

from benchmarks.chip.readers import program


def read(record):
    p = program(record)
    return None if p is None else p[0] / p[1] * 1e3
