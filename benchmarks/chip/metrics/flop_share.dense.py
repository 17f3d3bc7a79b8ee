"""Percent of the device's bf16 peak that the dense engine's cross term
reaches: 2·d·N flops per query row answered in the window, over the dense
program's device time.  N is the corpus size the configuration file
states.  The cross term runs at HIGHEST precision, about six bf16 passes
of the matrix unit, so the share cannot pass about 16.7%."""

from benchmarks.chip.dense import DENSE_PROGRAM, cross_term_flops
from benchmarks.chip.layout import Layout
from benchmarks.chip.readers import program

CONFIG = "sift1m-128-l2"


def read(record):
    p = program(record, DENSE_PROGRAM)
    peaks = record.get("peaks")
    if p is None or not peaks or not record.get("rows"):
        return None
    n = int(Layout().config(CONFIG)["n"])
    flops = cross_term_flops(record["rows"], n, record["d"])
    return 100.0 * flops / p[0] / peaks["bf16_flop_per_s"]
