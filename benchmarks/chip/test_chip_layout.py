"""CPU tests of BENCHMARK.json and the harness's discovery by name."""

import json
import re
import shutil
from pathlib import Path

import pytest

from benchmarks.chip.layout import BENCH_DIR, NAME_RE, ROOT, UNIT_RE, Layout

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
LIMIT_NAMES = {"unanswered", "unfilled_rows", "kth_err", "dist_err",
               "rank_err"}
# a width may never be reduced (the contract's list, for a system that
# runs no model: its record shapes)
WIDTH_RE = re.compile(r"(hidden|intermediate|latent|state|projection|head)"
                      r"|(_dim|_rank)$|^d$|^k$")


def _names():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            yield group, e["name"]
    for w in BENCH["workloads"]:
        yield "config", w["config"]
        yield "traffic", w["traffic"]
    for c in BENCH["configs"]:
        for key in c["reduced"]:
            yield "reduced", key


def test_top_level_keys_and_paths():
    assert set(BENCH) == TOP_KEYS
    assert BENCH["command"] == ["python3", "benchmarks/chip/run.py"]
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)
    assert (ROOT / "benchmarks" / "chip").resolve() == BENCH_DIR


@pytest.mark.parametrize("group", sorted(ENTRY_KEYS))
def test_entries_have_just_the_contract_keys(group):
    for e in BENCH[group]:
        metric = group in ("end_to_end", "per_layer")
        extra = {"workloads"} if metric else set()
        assert ENTRY_KEYS[group] <= set(e) <= ENTRY_KEYS[group] | extra, e


@pytest.mark.parametrize("kind,name", list(_names()))
def test_names_use_allowed_characters(kind, name):
    assert NAME_RE.match(name), (kind, name)


@pytest.mark.parametrize(
    "metric", BENCH["end_to_end"] + BENCH["per_layer"],
    ids=lambda m: m["name"])
def test_metric_unit_direction_and_source(metric):
    assert UNIT_RE.match(metric["unit"]), metric
    assert metric["better"] in ("lower", "higher")
    if "bound" in metric:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        assert "\n" not in metric["layer"] and 1 <= len(metric["layer"]) <= 200
    for cell in metric.get("workloads", []):
        assert cell in CELLS


def test_names_are_unique():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names)), group
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_and_reduced_keys(config):
    assert config["file"].startswith("benchmarks/chip/")
    body = json.loads((ROOT / config["file"]).read_text())
    assert body["name"] == config["name"]
    assert body["source"] == config["source"]
    assert body["reduced"] == config["reduced"]
    for key in config["reduced"]:
        assert key in body and not WIDTH_RE.search(key), key
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("workload", CELLS)
def test_every_cell_resolves_to_its_files(workload):
    layout = Layout()
    cell = layout.cell(workload)
    assert cell.chips in (1, 4)
    assert hasattr(layout.driver(cell.traffic["driver"]), "Driver")
    assert set(cell.limits) == LIMIT_NAMES
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e, (m["name"], workload)
        assert callable(layout.metric_reader(m["name"]).read)


def test_chips_within_the_share_of_four_chip_cells():
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 2)


DUMMY_READER = '''"""Query rows the window answered."""


def read(record):
    return record.get("rows")
'''


UNIFORM_BOX = '''"""Points uniform in a cube of side ``side``."""

import numpy as np


def make(n, d, seed, params):
    rng = np.random.default_rng(seed)
    return rng.uniform(0.0, params["side"], (n, d)).astype(np.float32)
'''


def test_new_config_traffic_driver_and_metric_are_found_as_new_files(
        tmp_path, capsys):
    """A cell added with new files only: a configuration with a new cloud
    generator, a traffic mix with a new driver kind, a per-layer metric
    and the cell's limits.  The
    copied files stay byte for byte as they were, and the new cell runs
    end to end (on the CPU, at a tiny size)."""
    bench_dir = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH_DIR, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}

    (bench_dir / "configs" / "uniform-tiny.json").write_text(json.dumps({
        "name": "uniform-tiny", "source": "test",
        "cloud": {"generator": "uniform_box", "side": 2.0},
        "n": 2048, "d": 3, "data_seed": 0, "metric": "l2",
        "backend": "trueknn", "spec": {"kind": "knn", "k": 4},
        "reduced": [],
    }))
    (bench_dir / "traffic" / "tiny-batches.json").write_text(json.dumps({
        "driver": "tiny_closed", "rows_per_search": 64, "replace": False,
        "jitter": 0.0, "warm_max": 3, "check_rows": 32,
    }))
    (bench_dir / "clouds" / "uniform_box.py").write_text(UNIFORM_BOX)
    shutil.copy(bench_dir / "drivers" / "closed_query.py",
                bench_dir / "drivers" / "tiny_closed.py")
    (bench_dir / "metrics" / "rows.tiny.py").write_text(DUMMY_READER)
    (bench_dir / "limits" / "uniform-tiny-closed.json").write_text(
        (bench_dir / "limits" / "kitti-frame-closed.json").read_text())
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "uniform-tiny", "source": "test",
        "file": "benchmarks/chip/configs/uniform-tiny.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "uniform-tiny-closed", "config": "uniform-tiny",
        "traffic": "tiny-batches", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "queries_per_s":
            m["workloads"].append("uniform-tiny-closed")
    bench["per_layer"].append({
        "name": "rows.tiny", "unit": "rows", "better": "higher",
        "source": "program_counter", "layer": "test",
        "moves": "queries_per_s", "workloads": ["uniform-tiny-closed"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    layout = Layout(tmp_path, bench_dir)
    cell = layout.cell("uniform-tiny-closed")
    assert layout.cloud("uniform_box").make(5, 3, 0, {"side": 1.0}).shape \
        == (5, 3)
    assert cell.traffic["driver"] == "tiny_closed"
    assert [m["name"] for m in cell.per_layer] == ["rows.tiny"]
    assert layout.metric_reader("rows.tiny").read({"rows": 7}) == 7
    for p, data in before.items():
        assert p.read_bytes() == data, p

    from benchmarks.chip.harness import run_cell

    rc = run_cell(layout, "uniform-tiny-closed", seed=2**31 + 5,
                  seconds=0.5, trace=True, t_start=0.0, require_tpu=False,
                  compile_cache=False)
    assert rc == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is True
    assert result["metrics"]["rows.tiny"]["value"] == result["attempted"]
    assert list(result)[-1] == "check"


def test_run_without_a_tpu_prints_no_result(capsys):
    from benchmarks.chip.harness import run_cell

    rc = run_cell(Layout(), CELLS[0], seed=1, seconds=1.0, trace=False,
                  t_start=0.0)
    captured = capsys.readouterr()
    assert rc != 0
    assert captured.out == ""
    assert "needs a TPU" in captured.err
