"""CPU tests of the cloud generators in ``clouds/``: every point is a
return the configured sensor could give."""

import numpy as np
import pytest

from benchmarks.chip.layout import Layout

LAYOUT = Layout()
CONFIGS = [LAYOUT.config(c["name"]) for c in LAYOUT.bench["configs"]]
LIDAR = [c for c in CONFIGS if c["cloud"]["generator"] == "lidar_street"]


def _make(config, n):
    cloud = LAYOUT.cloud(config["cloud"]["generator"])
    return cloud.make(n, int(config["d"]), int(config["data_seed"]),
                      config["cloud"])


@pytest.mark.parametrize("config", CONFIGS, ids=lambda c: c["name"])
def test_the_same_seed_makes_the_same_cloud(config):
    a, b = _make(config, 5000), _make(config, 5000)
    assert a.shape == (5000, int(config["d"])) and a.dtype == np.float32
    np.testing.assert_array_equal(a, b)
    assert len(np.unique(a, axis=0)) == len(a)


@pytest.mark.parametrize("config", LIDAR, ids=lambda c: c["name"])
def test_every_point_lies_on_a_beam_of_some_scan(config):
    p = config["cloud"]
    pts = _make(config, 20000).astype(np.float64)
    noise = 5 * p["range_noise_m"]
    step = p["speed_m_s"] / p["scan_rate_hz"]
    h = p["sensor_height_m"]
    lo, hi = np.deg2rad(p["elevation_deg"])
    scans = int(np.ceil(config["n"] / 250_000)) + 1
    seen = np.zeros(len(pts), bool)
    for j in range(scans):
        v = pts - [j * step, 0.0, h]
        r = np.linalg.norm(v, axis=1)
        el = np.arcsin(v[:, 2] / r)
        slack = noise / np.maximum(r, 1.0)
        seen |= ((r <= p["max_range_m"] + noise)
                 & (el >= lo - slack) & (el <= hi + slack))
    assert seen.all()
    on_road = np.abs(pts[:, 2]) <= noise
    on_facade = np.zeros(len(pts), bool)
    for y in p["facade_y_m"]:
        on_facade |= np.abs(pts[:, 1] - y) <= noise
    assert (on_road | on_facade).all()
    assert (pts[:, 2] <= p["facade_height_m"] + noise).all()


def test_a_scan_returns_no_ray_that_meets_nothing():
    from benchmarks.chip.clouds import lidar_street

    p = dict(LIDAR[0]["cloud"], facade_y_m=[], range_noise_m=0.0)
    pts = lidar_street.scan(p, 0.0, np.random.default_rng(0))
    # with no facades only the road returns, out to the stated range
    assert np.abs(pts[:, 2]).max() < 1e-9
    r = np.linalg.norm(pts - [0, 0, p["sensor_height_m"]], axis=1)
    assert r.max() <= p["max_range_m"]
    assert len(pts) < p["beams"] * 360 / p["azimuth_step_deg"]
