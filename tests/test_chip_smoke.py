"""chip_smoke.py on the CPU: its phases at a tiny size pass their float64
reference checks, the checks catch a wrong answer, and the entry point
refuses to run without a TPU (printing no result)."""

import importlib.util
from pathlib import Path

import numpy as np
import pytest

from repro.core import make_dataset


@pytest.fixture(scope="module")
def smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def kitti():
    return make_dataset("kitti", 2048, seed=0)


def test_phase_served_knn(smoke, kitti):
    clock = smoke.CompileClock()
    d = smoke.phase_served_knn(kitti, clock, batches=3, batch=64,
                               ref_queries=128)
    assert d.shape == (192, smoke.K) and np.isfinite(d).all()


def test_phase_self_knn(smoke):
    smoke.phase_self_knn(make_dataset("porto", 2048, seed=0),
                         smoke.CompileClock(), ref_queries=128)


def test_phase_range(smoke, kitti):
    from repro.api import KnnSpec, build_index, warm_default_radius

    warm = build_index(kitti, backend="trueknn").query(kitti[:64], KnnSpec(8))
    smoke.phase_range(kitti, warm_default_radius(warm.dists),
                      smoke.CompileClock(), batches=1, batch=64,
                      require_kernel=False)


@pytest.mark.parametrize("fault", ["id", "kth"])
def test_reference_check_catches_a_wrong_answer(smoke, kitti, fault):
    from repro.api import KnnSpec, build_index

    q = kitti[:32] + np.float32(0.01)
    res = build_index(kitti, backend="brute").query(q, KnnSpec(4))
    d, i = res.dists.copy(), res.idxs.copy()
    smoke.check_knn(kitti, q, d, i, 4)
    if fault == "id":
        far = int(np.argmax(np.linalg.norm(kitti - q[5], axis=1)))
        i[5, 1] = far
    else:
        d[7, 3] *= 1.01
    with pytest.raises(AssertionError):
        smoke.check_knn(kitti, q, d, i, 4)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_entry_point_refuses_cpu(smoke, capsys, argv):
    assert smoke.main(argv) != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "needs a TPU" in out.err
