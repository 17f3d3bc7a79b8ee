"""CPU tests of the peaks table, the bytes of a candidate slot, and the
readers that use them."""

import pytest

from benchmarks.chip.layout import Layout
from benchmarks.chip.peaks import PEAKS, peaks_for, slot_bytes


def test_v5e_peaks_are_the_published_ones():
    p = peaks_for("TPU v5 lite")
    assert p["bf16_flop_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert "TPU v5e" in p["source"]


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", "", "tpu v5 lite"])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError):
        peaks_for(kind)


def test_every_entry_names_its_source():
    for kind, p in PEAKS.items():
        assert p["source"] and p["hbm_bytes_per_s"] > 0, kind


@pytest.mark.parametrize("d,expected", [(1, 8), (2, 12), (3, 16), (8, 36)])
def test_slot_bytes(d, expected):
    assert slot_bytes(d) == expected


def test_slot_bytes_rejects_a_non_positive_dimension():
    with pytest.raises(ValueError):
        slot_bytes(0)


def _record(**kw):
    rec = {
        "trace": {"window_s": 2.0, "busy_s": 1.5, "devices": 1,
                  "programs": {"jit_run": {"seconds": 1.2, "runs": 4}}},
        "peaks": peaks_for("TPU v5 lite"),
        "d": 2,
        "searches": [{"round_tests": 10**9}, {"round_tests": 3 * 10**9}],
        "rows": 8192,
        "counters": {"brute_tail_queries": 16},
        "compiles": 0,
    }
    rec.update(kw)
    return rec


def test_round_bw_share_is_slot_bytes_over_program_time_and_peak():
    read = Layout().metric_reader("round_bw_share.oneshot").read
    want = 100.0 * 4e9 * 12 / 1.2 / 819e9
    assert read(_record()) == pytest.approx(want)
    assert read(_record(trace=None)) is None
    assert read(_record(peaks=None)) is None
    assert read(_record(searches=[{"round_tests": 0}])) is None


def test_oneshot_readers():
    layout = Layout()
    rec = _record()
    assert layout.metric_reader("device_ms.oneshot").read(rec) == \
        pytest.approx(300.0)
    assert layout.metric_reader("idle_share.oneshot").read(rec) == \
        pytest.approx(25.0)
    assert layout.metric_reader("tail_share.oneshot").read(rec) == \
        pytest.approx(100.0 * 16 / 8192)
    assert layout.metric_reader("compiles.oneshot").read(rec) == 0
    no_trace = _record(trace=None)
    assert layout.metric_reader("device_ms.oneshot").read(no_trace) is None
    assert layout.metric_reader("idle_share.oneshot").read(no_trace) is None


def test_serve_readers():
    import numpy as np

    layout = Layout()
    rec = _record(latency_s=np.linspace(0.1, 0.2, 101),
                  service_s=np.full(101, 0.05), batches=4, batch_rows=1000)
    assert layout.metric_reader("queue_ms.serve").read(rec) == \
        pytest.approx(145.0)
    assert layout.metric_reader("batch_rows.serve").read(rec) == 250.0
    assert layout.metric_reader("device_ms.serve").read(rec) == \
        pytest.approx(300.0)
    assert layout.metric_reader("batch_rows.serve").read(
        _record(batches=0)) is None
