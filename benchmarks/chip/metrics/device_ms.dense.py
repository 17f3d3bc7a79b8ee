"""Device milliseconds of the dense engine's program per search, from the
profiler's trace."""

from benchmarks.chip.dense import DENSE_PROGRAM
from benchmarks.chip.readers import program


def read(record):
    p = program(record, DENSE_PROGRAM)
    return None if p is None else p[0] / p[1] * 1e3
