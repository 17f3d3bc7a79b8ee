"""CPU tests of the comparison that decides ``correct``: the reference, the
control that has to fail it, and whole runs with the timed path broken.

Sizes are cut to what a test run holds; ``control.py`` reads the control
at the cell's own size.
"""

import io
import json

import numpy as np
import pytest

from benchmarks.chip import deploy, reference
from benchmarks.chip.harness import run_cell
from benchmarks.chip.layout import Layout

LAYOUT = Layout()
# The served cell is held out of BENCHMARK.json (PERF.md, Open questions)
# but keeps its driver, traffic mix and limits; it is checked as if listed.
LAYOUT.bench["workloads"] = LAYOUT.bench["workloads"] + [{
    "name": "kitti-serve-open", "config": "kitti-lidar-2p20",
    "traffic": "serve-open-points", "chips": 1, "why": "held out"}]
CELLS = [w["name"] for w in LAYOUT.bench["workloads"]]
SMALL_N = 4096


def _exact(pts, q, k):
    d2 = reference._sq_dists64(np.asarray(pts, np.float64),
                               np.asarray(q, np.float64))
    ids = np.argsort(d2, axis=1, kind="stable")[:, :k]
    return np.sqrt(np.take_along_axis(d2, ids, 1)).astype(np.float32), ids


def test_compare_reads_zero_for_the_exact_answer():
    rng = np.random.default_rng(0)
    pts = rng.uniform(size=(3000, 3)).astype(np.float32)
    q = rng.uniform(size=(50, 3)).astype(np.float32)
    d, i = _exact(pts, q, 8)
    got = reference.compare(pts, q, d, i, 8)
    assert got["unfilled_rows"] == 0 and got["rows_checked"] == 50
    assert got["rank_err"] == 0.0
    assert got["kth_err"] < 1e-6 and got["dist_err"] < 1e-6


def test_compare_flags_each_kind_of_wrong_row():
    rng = np.random.default_rng(1)
    pts = rng.uniform(size=(2000, 2)).astype(np.float32)
    q = rng.uniform(size=(10, 2)).astype(np.float32)
    d, i = _exact(pts, q, 4)
    bad = i.copy()
    bad[0, 1] = bad[0, 0]  # a repeated id
    bad[1, 0] = len(pts)  # an id outside the cloud
    dd = d.copy()
    dd[2, 3] = np.inf  # a missing neighbor
    got = reference.compare(pts, q, dd, bad, 4)
    assert got["unfilled_rows"] == 3
    far = i.copy()
    far[5, 3] = np.argmax(np.sum((pts - q[5]) ** 2, axis=1))  # not a kNN
    got = reference.compare(pts, q, d, far, 4)
    assert got["rank_err"] > 1.0 and got["dist_err"] > 1.0


def test_true_kth_matches_a_full_sort():
    rng = np.random.default_rng(2)
    pts = rng.normal(size=(5000, 3))
    q = rng.normal(size=(40, 3))
    d, _ = _exact(pts, q, 6)
    np.testing.assert_allclose(reference.true_kth(pts, q, 6), d[:, 5],
                               rtol=1e-6)


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_and_the_program_passes(workload):
    """The program's answers (CPU, small cloud of the cell's generator) pass
    the cell's limits; the bf16 control's answers on the same queries do
    not."""
    cell = LAYOUT.cell(workload)
    dep = deploy.build(dict(cell.config, n=SMALL_N),
                       LAYOUT.cloud(cell.config["cloud"]["generator"]))
    q = deploy.query_rows(deploy.seed_rng(3, 1), dep.points, 256,
                          cell.traffic)
    res = dep.index.query(q, dep.spec)
    sound = reference.compare(dep.points, q, res.dists, res.idxs, dep.k)
    sound["unanswered"] = 0
    assert reference.judge(sound, cell.limits), sound
    d, i = reference.bf16_knn(dep.points, q, dep.k)
    control = reference.compare(dep.points, q, d, i, dep.k)
    control["unanswered"] = 0
    assert not reference.judge(control, cell.limits), control


def _break(monkeypatch, fault):
    """Break the fused search, where every cell's answers are produced."""
    from repro.api.backends.trueknn import TrueKNNIndex

    orig = TrueKNNIndex._run_knn_fused

    def broken(self, *args, **kwargs):
        res = orig(self, *args, **kwargs)
        if res is None:
            return res
        m = len(res.dists)
        if fault == "half_left_out":
            res.dists[m // 2:] = np.inf
            res.idxs[m // 2:] = self.n_points
        elif fault == "answer_altered":
            res.idxs[0, 0] = (res.idxs[0, 0] + 1) % self.n_points
        return res

    monkeypatch.setattr(TrueKNNIndex, "_run_knn_fused", broken)


SMALL = {
    "config": {"n": SMALL_N},
    "traffic": {"rows_per_search": 256, "check_rows": 10**6, "rate": 100.0,
                "server": {"max_batch": 8}, "warm_sizes": [1, 2, 4, 8],
                "warm_step_sizes": [8],
                "warm_max_slices": 3},
}


@pytest.mark.parametrize("fault", ["none", "half_left_out", "answer_altered"])
@pytest.mark.parametrize("workload", CELLS)
def test_a_broken_timed_path_reads_not_correct(monkeypatch, workload, fault):
    """A whole run (CPU, small sizes, no chip) with the fused search broken
    underneath: ``correct`` comes out false for each fault, true for none."""
    _break(monkeypatch, fault)
    out, err = io.StringIO(), io.StringIO()
    rc = run_cell(LAYOUT, workload, seed=2**31 + 77, seconds=1.0,
                  trace=False, t_start=0.0, require_tpu=False,
                  compile_cache=False, overrides=SMALL, out=out, err=err)
    assert rc == 0
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert result["correct"] is (fault == "none"), result["check"]
    assert err.getvalue().strip().splitlines()[-1].startswith("check ")
