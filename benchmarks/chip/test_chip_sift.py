"""CPU tests of the dense cell's pieces: the SIFT-like descriptor
generator, the readers of its per-layer metrics, and the dense engine's
program and spans as a profiler trace shows them."""

import glob
import os

import numpy as np
import pytest

from benchmarks.chip import dense, spans, tracing
from benchmarks.chip.layout import Layout

LAYOUT = Layout()
CONFIG = LAYOUT.config("sift1m-128-l2")
CELL = "sift1m-batch-closed"


def _make(n, seed=None):
    cloud = LAYOUT.cloud(CONFIG["cloud"]["generator"])
    seed = CONFIG["data_seed"] if seed is None else seed
    return cloud.make(n, int(CONFIG["d"]), int(seed), CONFIG["cloud"])


def test_descriptors_are_uint8_values_of_lowe_length():
    p = CONFIG["cloud"]
    x = _make(20000)
    assert x.shape == (20000, 128) and x.dtype == np.float32
    assert (x >= 0).all() and (x <= p["max_value"]).all()
    np.testing.assert_array_equal(x, np.floor(x))
    # a unit vector times 512, floored: each component loses under 1, so
    # the length lies within sqrt(d) below 512 unless the cap cut it
    norm = np.linalg.norm(x.astype(np.float64), axis=1)
    assert (norm <= p["scale"] + 1e-3).all()
    uncapped = (x < p["max_value"]).all(1)
    assert uncapped.mean() > 0.99
    assert (norm[uncapped] >= p["scale"] - np.sqrt(128)).all()


def test_clipping_holds_each_bin_near_a_fifth_of_the_length():
    """The clip at 0.2 holds the largest component of most descriptors
    near a fifth of the length: the second normalisation lifts it only as
    far as the mass clipped away allows."""
    x = _make(20000).astype(np.float64)
    share = x.max(1) / np.linalg.norm(x, axis=1)
    assert np.median(share) < 0.3


def test_the_same_seed_makes_the_same_descriptors():
    np.testing.assert_array_equal(_make(3000), _make(3000))
    assert not np.array_equal(_make(3000), _make(3000, seed=1))


def test_a_grid_of_another_size_is_refused():
    cloud = LAYOUT.cloud("sift_like")
    with pytest.raises(ValueError, match="128 components"):
        cloud.make(10, 64, 0, CONFIG["cloud"])


def _record(seconds=2.0, runs=4, rows=40000):
    return {
        "trace": {"programs": {dense.DENSE_PROGRAM: {"seconds": seconds,
                                                     "runs": runs}},
                  "devices": 1, "busy_s": 2.5, "window_s": 5.0},
        "peaks": {"bf16_flop_per_s": 197e12},
        "rows": rows,
        "d": 128,
    }


def test_flop_share_reads_the_hand_computed_share():
    share = LAYOUT.metric_reader("flop_share.dense").read(_record())
    # 2 * 128 * 10^6 * 40,000 flops in 2 s, over 197 TFLOP/s
    assert share == pytest.approx(100 * 2.56e12 * 4 / 2.0 / 197e12)
    assert share == pytest.approx(2.5989847715736)


def test_device_ms_and_idle_share_read_the_dense_program():
    rec = _record()
    assert LAYOUT.metric_reader("device_ms.dense").read(rec) == 500.0
    assert LAYOUT.metric_reader("idle_share.dense").read(rec) == 50.0


@pytest.mark.parametrize(
    "name", ["device_ms.dense", "flop_share.dense", "idle_share.dense"])
def test_dense_readers_give_none_without_a_trace(name):
    reader = LAYOUT.metric_reader(name)
    assert reader.read({"rows": 10, "d": 128}) is None
    other = _record()
    other["trace"]["programs"] = {"jit_run": {"seconds": 1.0, "runs": 1}}
    if name != "idle_share.dense":
        assert reader.read(other) is None


def test_cross_term_flops_count_two_per_coordinate_pair():
    assert dense.cross_term_flops(3, 5, 7) == 210


def test_cell_reports_its_dense_metrics():
    cell = LAYOUT.cell(CELL)
    assert cell.chips == 1
    assert cell.config["backend"] == "brute"
    names = [m["name"] for m in cell.per_layer]
    assert names == ["device_ms.dense", "flop_share.dense",
                     "idle_share.dense"]


def test_cpu_trace_shows_the_dense_program_and_its_spans(tmp_path,
                                                        monkeypatch):
    """The brute backend's search, captured as the harness captures it
    plus the program's spans: ``brute.dispatch`` and ``brute.fetch`` nest
    in ``index.query``, and the HLO module of ``DENSE_PROGRAM`` carries
    the ``brute.dist`` and ``brute.select`` scopes."""
    import jax

    from repro import trace
    from repro.api import KnnSpec, build_index

    x = _make(2048)
    rng = np.random.default_rng(0)
    qs = (x[:64] + rng.normal(scale=16.0, size=(64, 128))).astype(np.float32)
    index = build_index(x, backend="brute")
    index.query(qs, KnnSpec(10))  # compile outside the capture

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = True
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    monkeypatch.setattr(tracing, "_active", True)
    trace.enable(True)
    try:
        with tracing.span(tracing.WINDOW_SPAN):
            index.query(qs, KnnSpec(10))
    finally:
        trace.enable(False)
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    rec = spans.load(path)
    host = {h[0]: h for h in rec["host"]}
    query = host["index.query"]
    for name in ("brute.dispatch", "brute.fetch"):
        s = host[name]
        assert query[1] <= s[1] and s[1] + s[2] <= query[1] + query[2]
    assert host["brute.dispatch"][1] < host["brute.fetch"][1]
    with open(path, "rb") as f:
        modules = spans.module_scopes(f.read())
    dense_runs = [m for name, m in modules.items()
                  if tracing.program_name(name) == dense.DENSE_PROGRAM]
    assert dense_runs
    scopes = set().union(*(m.values() for m in dense_runs))
    assert {"brute.dist", "brute.select"} <= scopes
