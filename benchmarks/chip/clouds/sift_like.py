"""SIFT-like image descriptors: gradient-orientation histograms normalised
and quantised as Lowe's descriptor is (IJCV 2004, section 6.1) and stored
as uint8, as the texmex ANN_SIFT1M set stores them.

A configuration gives the descriptor and the corpus under ``cloud``:

  cells, orientations  a cells x cells grid of histograms of that many
                       orientation bins: d = cells^2 * orientations
  clip                 the cap on each normalised component before the
                       second normalisation (Lowe: 0.2)
  scale, max_value     the renormalised vector times ``scale``, floored and
                       capped at ``max_value`` (Lowe: 512; uint8: 255)
  words                visual-word centres the descriptors cluster around
  word_shape           Gamma shape of each centre's raw bin magnitudes
  point_shape          Gamma shape (mean 1) of a descriptor's per-bin
                       factor on its centre's magnitudes
  noise_shape, noise_scale  Gamma of the raw magnitude added to every bin

Each descriptor picks a centre uniformly, draws its raw bins as
``centre * factor + noise``, then goes through Lowe's steps: unit length,
clip, unit length again, times ``scale``, floor, cap.  So every component
is an integer in [0, max_value], and a descriptor that no cap touched has
a length in [scale - sqrt(d), scale].
"""

from __future__ import annotations

import numpy as np

# descriptors made per step: bounds the working set to ~32 MB an array
BLOCK = 1 << 16


def lowe_normalise(raw: np.ndarray, params: dict) -> np.ndarray:
    """Lowe's normalisation of raw non-negative histograms (m, d):
    integer-valued float32 components in [0, max_value]."""
    v = raw / np.maximum(np.linalg.norm(raw, axis=1, keepdims=True), 1e-30)
    np.minimum(v, float(params["clip"]), out=v)
    v /= np.maximum(np.linalg.norm(v, axis=1, keepdims=True), 1e-30)
    v = np.floor(v * float(params["scale"]))
    np.minimum(v, float(params["max_value"]), out=v)
    return v.astype(np.float32)


def make(n: int, d: int, seed: int, params: dict) -> np.ndarray:
    """(n, d) float32 descriptors from ``seed``."""
    want = int(params["cells"]) ** 2 * int(params["orientations"])
    if d != want:
        raise ValueError(f"a {params['cells']}x{params['cells']}x"
                         f"{params['orientations']} descriptor has {want} "
                         f"components, the configuration states d={d}")
    rng = np.random.default_rng(seed)
    centres = rng.standard_gamma(float(params["word_shape"]),
                                 (int(params["words"]), d), np.float32)
    shape = float(params["point_shape"])
    noise_shape = float(params["noise_shape"])
    noise_scale = np.float32(params["noise_scale"])
    out = np.empty((n, d), np.float32)
    for s in range(0, n, BLOCK):
        m = min(BLOCK, n - s)
        raw = centres[rng.integers(0, len(centres), m)]
        raw *= rng.standard_gamma(shape, (m, d), np.float32)
        raw *= np.float32(1.0 / shape)
        raw += noise_scale * rng.standard_gamma(noise_shape, (m, d),
                                                np.float32)
        out[s:s + m] = lowe_normalise(raw, params)
    return out
