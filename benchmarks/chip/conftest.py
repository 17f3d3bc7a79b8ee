"""Test set-up shared by the benchmark's CPU tests.

``test_chip_check.test_a_broken_timed_path_reads_not_correct`` breaks the
fused TrueKNN search, where the grid cells' answers are produced, and
expects every cell of ``BENCHMARK.json`` to read not correct.  The dense
cell's answers come from the brute backend instead, so the fixture below
puts the same fault on that path too: each cell is then checked with its
own timed path broken.
"""

import dataclasses

import numpy as np
import pytest

BROKEN_PATH_TEST = "test_a_broken_timed_path_reads_not_correct"


def _faulty(res, fault: str, n: int):
    """``res`` with the named fault, as the fused search's is broken."""
    dists, idxs = np.array(res.dists), np.array(res.idxs)
    m = len(dists)
    if fault == "half_left_out":
        dists[m // 2:] = np.inf
        idxs[m // 2:] = n
    elif fault == "answer_altered":
        idxs[0, 0] = (idxs[0, 0] + 1) % n
    return dataclasses.replace(res, dists=dists, idxs=idxs)


@pytest.fixture(autouse=True)
def _break_the_dense_path_too(request, monkeypatch):
    if request.node.originalname != BROKEN_PATH_TEST:
        return
    fault = request.node.callspec.params["fault"]
    if fault == "none":
        return
    from repro.api.backends.brute import BruteIndex

    orig = BruteIndex._knn

    def broken(self, *args, **kwargs):
        return _faulty(orig(self, *args, **kwargs), fault, self.n_points)

    monkeypatch.setattr(BruteIndex, "_knn", broken)
