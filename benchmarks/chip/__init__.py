"""On-chip benchmark of the neighbor-search system.

``python benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the accelerator it is
started on and prints one JSON result line.  Everything that belongs to one
configuration, traffic mix, per-layer metric or cell lives in files of its
own, found by name (see ``layout.py``), so a new cell is new files only.
"""
