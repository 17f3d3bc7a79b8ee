"""CPU tests of the trace reduction: on hand-made records with known
answers, and on a record taken from a v5e run.

``testdata/v5e_kitti_frame_300ms.json.gz`` is the compact record
(``tracing.load``) of a ``kitti-frame-closed --trace 1`` run on one TPU v5e,
cut to the 300 ms around the host's work between two searches.
"""

import gzip
import json
from pathlib import Path

import pytest

from benchmarks.chip import tracing

RECORDED = (Path(__file__).resolve().parent / "testdata"
            / "v5e_kitti_frame_300ms.json.gz")


def _record():
    # window of 100 ns; ops overlap in [10, 30) and [25, 40), then [70, 80)
    return {
        "window_ns": 100,
        "host": [
            ["bench.window", 0, 100, "python"],
            ["bench.query", 0, 60, "python"],
            ["bench.generate", 40, 20, "python"],
            ["bench.result", 80, 20, "python"],
        ],
        "devices": {
            "/device:TPU:0": {
                "ops": [["fusion.1", 10, 20], ["gather.2", 25, 15],
                        ["fusion.1", 70, 10]],
                "modules": [["jit_run(7)", 10, 30], ["jit_other(3)", 70, 10]],
            },
        },
    }


def test_busy_is_the_union_of_op_intervals():
    s = tracing.reduce(_record())
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["busy_s"] == pytest.approx(40e-9)  # [10,40) + [70,80)
    assert s["devices"] == 1


def test_device_time_per_program_and_per_op():
    s = tracing.reduce(_record())
    assert s["programs"]["jit_run"] == {"seconds": pytest.approx(30e-9),
                                        "runs": 1}
    assert s["programs"]["jit_other"]["seconds"] == pytest.approx(10e-9)
    assert s["device_ops"][0] == ["fusion.1", pytest.approx(30e-9)]
    assert s["device_ops"][1] == ["gather.2", pytest.approx(15e-9)]


def test_idle_gaps_are_labelled_with_the_innermost_host_span():
    s = tracing.reduce(_record())
    # gaps: [0,10) in query, [40,70) mid 55 in generate, [80,100) in result
    assert s["idle_gaps"] == [
        ["bench.generate", pytest.approx(30e-9)],
        ["bench.result", pytest.approx(20e-9)],
        ["bench.query", pytest.approx(10e-9)],
    ]


def test_busy_is_averaged_over_the_devices_that_ran():
    rec = _record()
    rec["devices"]["/device:TPU:1"] = {"ops": [["fusion.1", 0, 100]],
                                       "modules": [["jit_run(7)", 0, 100]]}
    rec["devices"]["/device:TPU:2"] = {"ops": [], "modules": []}
    s = tracing.reduce(rec)
    assert s["devices"] == 2
    assert s["busy_s"] == pytest.approx(70e-9)
    assert s["programs"]["jit_run"]["seconds"] == pytest.approx(65e-9)


def test_program_name_drops_the_module_id():
    assert tracing.program_name("jit_run(123)") == "jit_run"
    assert tracing.program_name("jit_run") == "jit_run"


def test_spans_are_free_without_a_capture():
    with tracing.span("bench.query"):
        pass


def _brute_busy(rec, device):
    """Busy nanoseconds by marking every covered nanosecond."""
    import numpy as np

    covered = np.zeros(rec["window_ns"], bool)
    for _, s, d in rec["devices"][device]["ops"]:
        covered[s:s + d] = True
    return int(covered.sum())


def test_recorded_v5e_trace():
    with gzip.open(RECORDED, "rt") as f:
        rec = json.load(f)
    s = tracing.reduce(rec)
    (device,) = rec["devices"]
    assert device == "/device:TPU:0"
    assert s["devices"] == 1 and s["window_s"] == pytest.approx(0.3)
    assert s["busy_s"] == pytest.approx(_brute_busy(rec, device) / 1e9)
    assert 0.9 * s["window_s"] < s["busy_s"] < s["window_s"]
    # the fused program ran twice, back to back: the end of one search
    # and the start of the next
    runs = [m for m in rec["devices"][device]["modules"]
            if m[0].startswith("jit_run(")]
    assert s["programs"]["jit_run"]["runs"] == len(runs) == 2
    assert s["programs"]["jit_run"]["seconds"] == pytest.approx(
        sum(d for _, _, d in runs) / 1e9)
    # the longest idle gap lies between them (where three small programs
    # also ran), while the host finished the first search's query call
    between = runs[1][1] - (runs[0][1] + runs[0][2])
    (label, gap), *rest = s["idle_gaps"]
    assert label == "bench.query"
    assert 0.5 * between < gap * 1e9 <= between
    assert all(g <= gap for _, g in rest)
    assert sum(g for _, g in s["idle_gaps"]) <= s["window_s"] - s["busy_s"]
    assert s["device_ops"][0][0].startswith("while.")
