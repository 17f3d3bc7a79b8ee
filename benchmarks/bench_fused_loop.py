"""Fused radius-growth loop benchmark: dispatch proof, identity, latency.

The trueknn monolith's multi-round expand-until-k search runs as ONE
jitted ``lax.while_loop`` device program however many rounds the radius
schedule takes; the pre-fusion driver (kept behind ``fused=False`` as
the oracle) pays one dispatch per round plus the brute tail.  This
benchmark proves the acceptance gates at bench scale:

* **one dispatch** — counter-proven: a 2-round and an 8-round search
  each increment the backend's dispatch counter by exactly 1, while the
  host-loop driver burns at least one dispatch per round.
* **identity** — fused answers are ``np.array_equal`` to the host-loop
  driver AND to brute force (dists, idxs, found).
* **round latency is flat where dispatch overhead dominates** — the
  point of fusing: on the small-batch overhead probe (the
  latency-sensitive serving regime) an 8-round search must cost at
  most 1.5x a 2-round search.  The probe runs on a *uniform* cloud:
  each round scores ``stencil x cap`` bucket slots per query, and on
  heavy-tailed clouds (porto) the coarse-grid rounds' caps grow into
  the thousands — cap-proportional candidate scoring that any driver
  pays, which would swamp the launch overhead the gate is about.  On
  uniform data every round's cap stays small (8-64 at bench scale),
  so the probe isolates the dispatch component.  The two round counts
  are timed as interleaved pairs and the gate takes the median of
  pairwise ratios, cancelling the seconds-long noise windows shared
  CI boxes exhibit.  Full-batch porto latencies are reported too, but
  there extra rounds buy extra grid searches — real work — so they
  inform rather than gate.

Round counts are steered with explicit ``start_radius`` seeds derived
from the batch's true k-th-NN distances (a seed never changes answers).
Emits CSV rows via the harness contract and returns a summary dict that
benchmarks/run.py serializes to BENCH_fused.json.
"""

from __future__ import annotations

import time

import numpy as np

from repro.api import HybridSpec, KnnSpec, build_index
from repro.core import make_dataset

from .common import emit


def _time_best(fn, reps: int) -> float:
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _same(a, b, k=None) -> bool:
    ok = np.array_equal(a.dists, b.dists) and np.array_equal(a.idxs, b.idxs)
    if getattr(a, "found", None) is not None and \
            getattr(b, "found", None) is not None:
        fa, fb = a.found, b.found
        if k is not None:  # found past k is backend-defined (HybridSpec)
            fa, fb = np.minimum(fa, k), np.minimum(fb, k)
        ok = ok and np.array_equal(fa, fb)
    return bool(ok)


def main(n=20_000, k=8, n_queries=512, reps=3) -> dict:
    pts = make_dataset("porto", n, seed=0)
    rng = np.random.default_rng(1)
    qs = (
        pts[rng.integers(0, n, n_queries)]
        + rng.normal(scale=0.05, size=(n_queries, pts.shape[1]))
    ).astype(np.float32)

    fused = build_index(pts, backend="trueknn")
    host = build_index(pts, backend="trueknn", fused=False)
    brute = build_index(pts, backend="brute")
    warm = fused.query(qs, KnnSpec(k))  # warms sampling + the default jit
    host.query(qs, KnnSpec(k))
    kth = warm.dists[:, -1]
    r_top = float(kth[np.isfinite(kth)].max()) * 1.05

    runs = {}
    for label, r0 in (("rounds2", r_top / 2), ("rounds8", r_top / 128)):
        spec = KnnSpec(k, start_radius=r0)
        before = fused.stats()["dispatches"]
        res = fused.query(qs, spec)  # also warms this schedule's program
        disp = fused.stats()["dispatches"] - before
        h_before = host.stats()["dispatches"]
        hres = host.query(qs, spec)
        host_disp = host.stats()["dispatches"] - h_before
        ident = _same(res, hres) and _same(res, brute.query(qs, KnnSpec(k)))
        fused_s = _time_best(lambda s=spec: fused.query(qs, s), reps)
        host_s = _time_best(lambda s=spec: host.query(qs, s), reps)
        runs[label] = {
            "start_radius": round(r0, 6),
            "rounds": int(res.n_rounds),
            "fused_dispatches": int(disp),
            "host_dispatches": int(host_disp),
            "identity": ident,
            "fused_us_per_query": round(fused_s * 1e6 / n_queries, 2),
            "host_us_per_query": round(host_s * 1e6 / n_queries, 2),
            "fused_s": fused_s,
        }
        emit(
            f"fused_loop/{label}",
            fused_s * 1e6 / n_queries,
            f"rounds={res.n_rounds} dispatches={disp} "
            f"host_dispatches={host_disp} identity={ident} "
            f"host_us={host_s * 1e6 / n_queries:.1f}",
        )

    # hybrid rides the same driver: one dispatch, same identity contract
    # (found past k is backend-defined, so it compares clipped at k)
    r_mid = r_top / 4
    hy = fused.query(qs, HybridSpec(k, r_mid))
    hybrid_ident = _same(
        hy, host.query(qs, HybridSpec(k, r_mid)), k=k
    ) and _same(hy, brute.query(qs, HybridSpec(k, r_mid)), k=k)
    hybrid_disp = int(hy.timings.get("fused_dispatches", 0))
    emit(
        "fused_loop/hybrid",
        hybrid_disp,
        f"identity={hybrid_ident} dispatches={hybrid_disp}",
    )

    # the latency gate runs where launch overhead dominates: a tiny batch
    # on a uniform cloud, whose grids keep small caps at every round
    # (see the module docstring), best-of timing to shrug off box noise
    probe_reps = max(reps, 5)
    u_pts = make_dataset("uniform", min(n, 8000), seed=0)
    u_qs = (
        u_pts[rng.integers(0, len(u_pts), 64)]
        + rng.normal(scale=0.01, size=(64, u_pts.shape[1]))
    ).astype(np.float32)
    u_fused = build_index(u_pts, backend="trueknn")
    u_host = build_index(u_pts, backend="trueknn", fused=False)
    u_kth = u_fused.query(u_qs, KnnSpec(k)).dists[:, -1]
    u_host.query(u_qs, KnnSpec(k))
    u_top = float(u_kth[np.isfinite(u_kth)].max()) * 1.05
    q2 = u_qs[:2]
    spec2 = KnnSpec(k, start_radius=u_top / 2)
    spec8 = KnnSpec(k, start_radius=u_top / 128)
    pf2 = u_fused.query(q2, spec2)  # warm both shapes' programs
    pf8 = u_fused.query(q2, spec8)
    probe_ident = _same(pf2, u_host.query(q2, spec2)) and _same(
        pf8, u_host.query(q2, spec8)
    )
    # interleave the 2-round and 8-round timings rep by rep and take the
    # median of pairwise ratios: box-noise windows (vCPU bursts, shared
    # hosts) last seconds and hit both searches of a pair equally, so
    # the common mode cancels where sequential best-of-N would not
    n_pairs = 3 * probe_reps
    f_pairs, h_pairs, t2s, t8s = [], [], [], []
    for _ in range(n_pairs):
        t0 = time.perf_counter()
        u_fused.query(q2, spec2)
        t2 = time.perf_counter() - t0
        t0 = time.perf_counter()
        u_fused.query(q2, spec8)
        t8 = time.perf_counter() - t0
        f_pairs.append(t8 / t2)
        t2s.append(t2)
        t8s.append(t8)
        t0 = time.perf_counter()
        u_host.query(q2, spec2)
        h2 = time.perf_counter() - t0
        t0 = time.perf_counter()
        u_host.query(q2, spec8)
        h_pairs.append((time.perf_counter() - t0) / h2)
    probe = {
        "rounds2": {"rounds": int(pf2.n_rounds),
                    "fused_s": float(np.median(t2s))},
        "rounds8": {"rounds": int(pf8.n_rounds),
                    "fused_s": float(np.median(t8s))},
    }
    ratio = float(np.median(f_pairs))
    host_ratio = float(np.median(h_pairs))
    emit(
        "fused_loop/overhead_probe",
        ratio,
        f"fused rounds8/rounds2={ratio:.2f} host={host_ratio:.2f} "
        f"rounds={probe['rounds2']['rounds']}/{probe['rounds8']['rounds']} "
        f"identity={probe_ident} (uniform cloud, Q=2, median of "
        f"{n_pairs} interleaved pairs)",
    )
    batch_ratio = runs["rounds8"]["fused_s"] / runs["rounds2"]["fused_s"]
    for r in runs.values():
        del r["fused_s"]
    summary = {
        "n": n,
        "k": k,
        "n_queries": n_queries,
        "runs": runs,
        "hybrid": {"identity": hybrid_ident, "dispatches": hybrid_disp},
        "overhead_probe": {
            "dataset": "uniform",
            "identity": probe_ident,
            "rounds": {lbl: v["rounds"] for lbl, v in probe.items()},
            "fused_rounds8_over_rounds2": round(ratio, 3),
            "host_rounds8_over_rounds2": round(host_ratio, 3),
            "fused_us": {
                lbl: round(v["fused_s"] * 1e6, 1) for lbl, v in probe.items()
            },
        },
        "batch_rounds8_over_rounds2": round(batch_ratio, 3),
        "gates": {
            "one_dispatch": bool(
                runs["rounds2"]["fused_dispatches"] == 1
                and runs["rounds8"]["fused_dispatches"] == 1
                and hybrid_disp == 1
            ),
            "identity": bool(
                runs["rounds2"]["identity"]
                and runs["rounds8"]["identity"]
                and hybrid_ident
                and probe_ident
            ),
            "rounds_differ": bool(
                runs["rounds8"]["rounds"] - runs["rounds2"]["rounds"] >= 3
                and probe["rounds8"]["rounds"]
                - probe["rounds2"]["rounds"] >= 3
            ),
            "rounds8_le_1p5x_rounds2": bool(ratio <= 1.5),
        },
    }
    emit(
        "fused_loop/summary",
        ratio,
        " ".join(f"{g}={v}" for g, v in summary["gates"].items()),
    )
    return summary


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import json

    print(json.dumps(main(), indent=2, default=str))
