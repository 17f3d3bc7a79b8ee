"""Percent of the traced window in which the device ran no operation."""

from benchmarks.chip.readers import idle_share


def read(record):
    return idle_share(record)
