"""Scans of a rotating multi-beam LiDAR driven down a street, ray-cast
against flat ground and two building facades.

A configuration gives the sensor and the scene under ``cloud``:

  beams, elevation_deg    beam count, spread evenly over [low, high] degrees
  azimuth_step_deg        azimuth between two firings of a beam
  max_range_m             no return from farther
  range_noise_m           Gaussian noise on each return's range
  sensor_height_m         the sensor's height above the road
  scan_rate_hz, speed_m_s scans a second, and how fast the vehicle drives
  facade_y_m              lateral positions of the two facades (one < 0 < one)
  facade_height_m         facade height above the road

Every point is the first surface a beam of some scan meets within range:
the road (z = 0) or a facade, below its top.  A beam that meets neither
returns nothing.  Scans follow the vehicle along x until they hold ``n``
points or more; the cloud is ``n`` of those points drawn at random, so a
smaller ``n`` thins the scans evenly instead of cutting one off.
"""

from __future__ import annotations

import numpy as np


def scan(params: dict, x0: float, rng) -> np.ndarray:
    """(m, 3) float64 returns of one full sweep from a sensor at x = x0."""
    lo, hi = params["elevation_deg"]
    elev = np.deg2rad(np.linspace(lo, hi, int(params["beams"])))
    az = np.deg2rad(np.arange(0.0, 360.0, float(params["azimuth_step_deg"])))
    e, a = np.meshgrid(elev, az, indexing="ij")
    e, a = e.ravel(), a.ravel()
    dx, dy, dz = np.cos(e) * np.cos(a), np.cos(e) * np.sin(a), np.sin(e)
    h = float(params["sensor_height_m"])
    with np.errstate(divide="ignore", invalid="ignore"):
        t = np.where(dz < 0, h / -dz, np.inf)  # the road
        for wall in params["facade_y_m"]:
            tw = np.where(dy * wall > 0, wall / dy, np.inf)
            zw = h + tw * dz
            hit = (zw >= 0) & (zw <= float(params["facade_height_m"]))
            t = np.minimum(t, np.where(hit, tw, np.inf))
    keep = t <= float(params["max_range_m"])
    t = t[keep] + rng.normal(0.0, float(params["range_noise_m"]), keep.sum())
    return np.stack([x0 + t * dx[keep], t * dy[keep], h + t * dz[keep]], 1)


def make(n: int, d: int, seed: int, params: dict) -> np.ndarray:
    """(n, 3) float32 cloud of the scans ``params`` describes."""
    if d != 3:
        raise ValueError(f"a LiDAR scan is 3-dimensional, not {d}")
    rng = np.random.default_rng(seed)
    step = float(params["speed_m_s"]) / float(params["scan_rate_hz"])
    scans, have = [], 0
    while have < n:
        scans.append(scan(params, len(scans) * step, rng))
        have += len(scans[-1])
    pts = np.concatenate(scans)
    return pts[np.sort(rng.choice(len(pts), n, replace=False))].astype(
        np.float32)
