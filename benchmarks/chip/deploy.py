"""Build a configuration's deployment: the resident cloud, the index and
the query spec, from the configuration file and the cloud generator it
names (``clouds/<generator>.py``).

The cloud comes from the configuration's ``data_seed``, not from a run's
``--seed``: the grid shapes, and so the compiled programs, follow from the
cloud, so every run of a cell serves the same deployment from a warm
compile cache.  A run's seed draws its queries and arrivals.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Deployment:
    points: np.ndarray
    index: object
    spec: object
    k: int
    config: dict


def build(config: dict, cloud) -> Deployment:
    """Make the cloud with the generator module ``cloud`` and build the
    index that ``config`` describes."""
    from repro.api import KnnSpec, build_index

    n, d = int(config["n"]), int(config["d"])
    pts = cloud.make(n, d, int(config["data_seed"]), config["cloud"])
    if pts.shape != (n, d) or pts.dtype != np.float32:
        raise ValueError(
            f"generator {config['cloud']['generator']!r} made a {pts.dtype} "
            f"cloud of shape {pts.shape}, the configuration states float32 "
            f"({n}, {d})"
        )
    spec_cfg = config["spec"]
    if spec_cfg["kind"] != "knn" or config["metric"] != "l2":
        raise ValueError(
            f"only L2 kNN specs are benchmarked, got {spec_cfg} / "
            f"{config['metric']!r}"
        )
    k = int(spec_cfg["k"])
    index = build_index(pts, backend=config["backend"],
                        **config.get("backend_cfg", {}))
    return Deployment(points=pts, index=index, spec=KnnSpec(k), k=k,
                      config=config)


# seed of the set-up's own queries: fixed, so that set-up does the same
# work and obtains the same programs in every run of a cell
SETUP_SEED = 0


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """Independent generator ``stream`` of a run's seed (any integer)."""
    return np.random.default_rng([int(seed) % (1 << 64), int(stream)])


def query_rows(rng, points, m: int, traffic: dict) -> np.ndarray:
    """``m`` query rows drawn as the traffic mix says: cloud rows (without
    replacement where ``replace`` is false) plus Gaussian jitter of
    ``jitter`` in the cloud's units (0: the rows themselves)."""
    n = len(points)
    if traffic.get("replace", True):
        rows = points[rng.integers(0, n, m)]
    else:
        rows = points[rng.choice(n, m, replace=False)]
    jitter = float(traffic.get("jitter", 0.0))
    if jitter:
        rows = rows + rng.normal(scale=jitter, size=rows.shape)
    return np.asarray(rows, np.float32)
