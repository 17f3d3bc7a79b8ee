"""CPU tests of the program's spans and scopes in a trace (``spans.py``): on
hand-made records with known answers, on a profiler capture of real
searches on the CPU, and on a record taken from a v5e run.

``testdata/v5e_kitti_spans_300ms.json.gz`` is the record (``spans.load``)
of a kitti-frame-closed window on one TPU v5e, traced with the program's
spans on and HLO protos kept, cut to the 300 ms around the host's work
between two searches.
"""

import glob
import gzip
import json
import os
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import spans, tracing

RECORDED = (Path(__file__).resolve().parent / "testdata"
            / "v5e_kitti_spans_300ms.json.gz")


def _record():
    # one thread: a window, a search [0, 90) holding dispatch [5, 20) and
    # fetch [20, 80); a round loop `while` [20, 70) on the device whose
    # body ops overlap it, two rounds of two branches, and a tail
    return {
        "window_ns": 100,
        "host": [
            ["bench.window", 0, 100, "python"],
            ["bench.query", 0, 95, "python"],
            ["index.query", 0, 90, "python"],
            ["trueknn.dispatch", 5, 15, "python"],
            ["trueknn.fetch", 20, 60, "python"],
            ["bench.generate", 95, 5, "python"],
        ],
        "devices": {
            "/device:TPU:0": {
                "ops": [["while.1", 20, 50], ["while.2", 20, 20],
                        ["fusion.3", 25, 10], ["while.4", 40, 20],
                        ["fusion.5", 72, 5]],
                "modules": [["jit_run(1)", 20, 60]],
                "scoped": [
                    ["trueknn.fused", 20, 50],
                    ["trueknn.fused/trueknn.round.b0", 20, 20],
                    ["trueknn.fused/trueknn.round.b0", 25, 10],
                    ["trueknn.fused/trueknn.round.b1", 40, 20],
                    ["trueknn.fused/trueknn.tail", 72, 5],
                ],
            },
        },
    }


def test_scope_time_is_the_union_of_its_ops():
    scopes = spans.scope_table(_record())
    # the b0 `while` [20, 40) holds its body op [25, 35): counted once
    assert scopes["trueknn.round.b0"] == pytest.approx(20e-9)
    assert scopes["trueknn.round.b1"] == pytest.approx(20e-9)
    assert scopes["trueknn.round"] == pytest.approx(40e-9)
    assert scopes["trueknn.tail"] == pytest.approx(5e-9)
    # the round loop [20, 70) plus the tail [72, 77)
    assert scopes["trueknn.fused"] == pytest.approx(55e-9)
    assert scopes["trueknn"] == pytest.approx(55e-9)


def test_span_self_time_leaves_out_direct_children():
    table = spans.span_table(_record()["host"])
    assert table["index.query"] == {"count": 1,
                                    "seconds": pytest.approx(90e-9),
                                    "self_seconds": pytest.approx(15e-9)}
    assert table["bench.query"]["self_seconds"] == pytest.approx(5e-9)
    assert table["bench.window"]["self_seconds"] == pytest.approx(0.0)
    assert table["trueknn.fetch"]["self_seconds"] == pytest.approx(60e-9)


def test_self_time_is_per_thread():
    host = [["server.batch", 0, 50, "worker"],
            ["index.query", 10, 20, "client"],
            ["server.execute", 10, 30, "worker"]]
    table = spans.span_table(host)
    assert table["server.batch"]["self_seconds"] == pytest.approx(20e-9)
    assert table["index.query"]["self_seconds"] == pytest.approx(20e-9)


def test_idle_gaps_take_the_innermost_program_span():
    s = tracing.reduce(_record())
    # gaps [0, 20) mid 10 in dispatch, [70, 72) in fetch, [77, 100) mid
    # 88 in index.query
    assert s["idle_gaps"] == [
        ["index.query", pytest.approx(23e-9)],
        ["trueknn.dispatch", pytest.approx(20e-9)],
        ["trueknn.fetch", pytest.approx(2e-9)],
    ]


def test_program_scope_keeps_only_program_scopes():
    assert spans._program_scope(
        "jit(run)/trueknn.fused/while/body/branch_1_fun/trueknn.round.b1/"
        "while/body/gather") == "trueknn.fused/trueknn.round.b1"
    assert spans._program_scope("jit(run)/while/body/add") == ""


def _capture(tmp_path, monkeypatch, work):
    """Run ``work()`` inside ``bench.window`` under a CPU profiler capture
    with the harness's and the program's spans on; the trace's path."""
    import jax

    from repro import trace

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = True
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    monkeypatch.setattr(tracing, "_active", True)
    trace.enable(True)
    try:
        with tracing.span(tracing.WINDOW_SPAN):
            work()
    finally:
        trace.enable(False)
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    return path


def _inside(outer, inner):
    return (outer[3] == inner[3] and outer[1] <= inner[1]
            and inner[1] + inner[2] <= outer[1] + outer[2])


def _one(record, name):
    (ev,) = [h for h in record["host"] if h[0] == name]
    return ev


def test_cpu_capture_nests_the_search_under_the_harness_span(
        tmp_path, monkeypatch):
    from repro.api import KnnSpec, build_index

    rng = np.random.default_rng(0)
    index = build_index(rng.random((2000, 3), dtype=np.float32))
    qs = rng.random((64, 3), dtype=np.float32)
    index.query(qs, KnnSpec(4))  # compile outside the capture

    def work():
        with tracing.span("bench.query"):
            index.query(qs, KnnSpec(4))

    rec = spans.load(_capture(tmp_path, monkeypatch, work))
    query = _one(rec, "index.query")
    assert _inside(_one(rec, "bench.query"), query)
    for name in ("trueknn.schedule", "trueknn.dispatch", "trueknn.fetch",
                 "trueknn.finish"):
        assert _inside(query, _one(rec, name)), name
    table = spans.reduce(rec)["spans"]
    assert table["index.query"]["count"] == 1
    assert table["index.query"]["self_seconds"] < table["index.query"][
        "seconds"]


def test_cpu_capture_of_a_served_batch(tmp_path, monkeypatch):
    from repro.api import KnnSpec, NeighborServer, build_index

    rng = np.random.default_rng(1)
    server = NeighborServer(build_index(rng.random((2000, 3),
                                                   dtype=np.float32)))
    qs = rng.random((16, 3), dtype=np.float32)
    server.submit(qs, KnnSpec(4)).result()  # compile outside the capture

    def work():
        server.submit(qs + 0.5, KnnSpec(4)).result()

    rec = spans.load(_capture(tmp_path, monkeypatch, work))
    batch = _one(rec, "server.batch")
    execute = _one(rec, "server.execute")
    assert _inside(batch, execute)
    assert _inside(execute, _one(rec, "trueknn.dispatch"))


def test_hlo_protos_give_each_instruction_its_scope(tmp_path, monkeypatch):
    from repro.api import KnnSpec, build_index

    rng = np.random.default_rng(2)
    index = build_index(rng.random((2000, 3), dtype=np.float32))
    qs = rng.random((64, 3), dtype=np.float32)
    # a tiny start radius: several rounds, then the brute tail
    spec = KnnSpec(4, start_radius=1e-4)
    assert index.query(qs, spec).n_rounds >= 3

    path = _capture(tmp_path, monkeypatch, lambda: index.query(qs, spec))
    with open(path, "rb") as f:
        modules = spans.module_scopes(f.read())
    runs = [m for name, m in modules.items() if name.startswith("jit_run(")]
    assert runs
    scopes = set().union(*(m.values() for m in runs))
    for want in ("trueknn.fused", "trueknn.fused/trueknn.round.b0",
                 "trueknn.fused/trueknn.round.b1",
                 "trueknn.fused/trueknn.tail"):
        assert want in scopes, want
    assert all(s.startswith("trueknn.fused") for s in scopes)


def _recorded():
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)


def _open_at(host, name, t):
    (ev,) = [h for h in host if h[0] == name and h[1] <= t < h[1] + h[2]]
    return ev


def test_recorded_v5e_spans_share_the_device_clock():
    """Each run of the fused program starts after its search's dispatch
    span opened and ends before its fetch span closed."""
    rec = _recorded()
    host, wlen = rec["host"], rec["window_ns"]
    (dev,) = rec["devices"].values()
    runs = [m for m in dev["modules"] if m[0].startswith("jit_run(")]
    assert len(runs) == 2
    first, second = sorted(runs, key=lambda m: m[1])
    # the first search's run ends inside the cut, the next one's starts
    end = first[1] + first[2]
    assert 0 < end < second[1] < wlen
    fetch = _open_at(host, "trueknn.fetch", end - 1)
    assert _inside(_open_at(host, "index.query", end - 1), fetch)
    assert end <= fetch[1] + fetch[2]
    query = _open_at(host, "index.query", second[1])
    (dispatch,) = [h for h in host
                   if h[0] == "trueknn.dispatch" and _inside(query, h)]
    assert dispatch[1] <= second[1]


def test_recorded_v5e_scopes_cover_the_fused_program():
    rec = _recorded()
    (dev,) = rec["devices"].values()
    run_s = sum(d for name, _, d in dev["modules"]
                if name.startswith("jit_run(")) / 1e9
    scopes = spans.reduce(rec)["scopes"]
    assert 0.9 * run_s <= scopes["trueknn.fused"] <= run_s
    assert scopes["trueknn.round"] + scopes["trueknn.tail"] <= (
        scopes["trueknn.fused"])
    assert scopes["trueknn.tail"] > 0


def test_recorded_v5e_idle_gaps_carry_program_spans():
    """On the same record, ``tracing.reduce`` keeps every number it gave
    without the program's spans; only the labels of idle gaps move from
    the harness's span to the program's."""
    rec = _recorded()
    bench_only = dict(rec, host=[h for h in rec["host"]
                                 if h[0].startswith(tracing.SPAN_PREFIX)])
    with_program, without = tracing.reduce(rec), tracing.reduce(bench_only)
    for key in ("window_s", "busy_s", "devices", "programs", "device_ops"):
        assert with_program[key] == without[key], key
    assert ([g for _, g in with_program["idle_gaps"]]
            == [g for _, g in without["idle_gaps"]])
    (label, _), *_ = with_program["idle_gaps"]
    assert label.startswith(("index.", "trueknn."))
    assert without["idle_gaps"][0][0] == "bench.query"
