"""Fused on-device radius-growth loop: identity and dispatch-count tests.

The trueknn backend's multi-round expand-until-k search runs as ONE
jitted ``lax.while_loop`` device program (``repro.core.fused_loop``)
instead of one dispatch per round.  The host round loop survives behind
``fused=False`` as the oracle: every test here pins the fused driver's
answers bit for bit against it (and against brute force), across
metrics, spec shapes and the degenerate corners, then proves the "one
dispatch however many rounds" contract on the backend's dispatch
counter — for the monolith and for the placed sharded fabric.
"""

import numpy as np
import pytest

from repro.api import HybridSpec, KnnSpec, build_index, get_metric
from repro.core import make_dataset

PTS = make_dataset("porto", 500, seed=4)
QS = np.concatenate(
    [
        make_dataset("porto", 20, seed=11),
        np.float32([[40.0, 40.0], [-35.0, 20.0]]),  # far out: sparse balls
    ]
)
METRICS = ["l2", "l1", "linf", "cosine"]


def _radius(metric, pct=60.0):
    D = get_metric(metric).pairwise(QS, PTS)
    return float(np.percentile(np.sort(D, 1)[:, 4], pct))


def _pair(**cfg):
    return (
        build_index(PTS, backend="trueknn", **cfg),
        build_index(PTS, backend="trueknn", fused=False, **cfg),
    )


def _same(a, b):
    assert np.array_equal(a.dists, b.dists)
    assert np.array_equal(a.idxs, b.idxs)
    if (
        getattr(a, "found", None) is not None
        and getattr(b, "found", None) is not None
    ):
        assert np.array_equal(a.found, b.found)


def _close(a, b):
    # cosine runs through the l2_view companion cloud: exact vs the host
    # driver (same mapping), approximate vs brute's direct cosine engine
    assert np.allclose(a.dists, b.dists, rtol=1e-4, atol=1e-6)


# --------------------------------------------------- identity vs the oracles


@pytest.mark.parametrize("metric", METRICS)
def test_fused_identity_matrix(metric):
    """The acceptance property: fused answers equal the host-loop driver
    AND brute force — plain kNN, hybrid, and a stop_radius schedule that
    leaves rows unfilled (the far-out queries' balls are sparse)."""
    r = _radius(metric)
    fused, host = _pair()
    brute = build_index(PTS, backend="brute")
    for spec in (KnnSpec(5), HybridSpec(5, r)):
        f = fused.query(QS, spec, metric=metric)
        _same(f, host.query(QS, spec, metric=metric))
        b = brute.query(QS, spec, metric=metric)
        if metric == "cosine":
            _close(f, b)
        else:
            assert np.array_equal(f.dists, b.dists)
            assert np.array_equal(f.idxs, b.idxs)
            if f.found is not None and b.found is not None:
                # found past k is backend-defined (HybridSpec contract):
                # compare the resolved/unfilled structure, not raw counts
                assert np.array_equal(
                    np.minimum(f.found, 5), np.minimum(b.found, 5)
                )
    if metric in ("l2", "cosine"):
        # stop_radius needs a radius-scheduled engine (l1/linf route to
        # the dense fallback): fused vs host; the far rows really are
        # unfilled — the tail contract under the cap
        spec = KnnSpec(5, stop_radius=r)
        f = fused.query(QS, spec, metric=metric)
        _same(f, host.query(QS, spec, metric=metric))
        assert (f.found < 5).any() and np.isinf(f.dists).any()


@pytest.mark.parametrize("metric", ["l2", "cosine"])
def test_fused_identity_self_queries(metric):
    fused, host = _pair()
    brute = build_index(PTS, backend="brute")
    f = fused.query(None, KnnSpec(4), metric=metric)
    _same(f, host.query(None, KnnSpec(4), metric=metric))
    b = brute.query(None, KnnSpec(4), metric=metric)
    _close(f, b) if metric == "cosine" else _same(f, b)
    assert not (f.idxs == np.arange(len(PTS))[:, None]).any()


def test_fused_empty_batch():
    fused, host = _pair()
    q0 = np.empty((0, 2), np.float32)
    f = fused.query(q0, KnnSpec(3))
    h = host.query(q0, KnnSpec(3))
    assert f.dists.shape == h.dists.shape == (0, 3)


def test_fused_max_rounds_bailout():
    """A schedule that exhausts its round budget (slow growth, 3 rounds)
    bails to the exact brute tail identically in both drivers."""
    fused, host = _pair(growth=1.01, max_rounds=3)
    brute = build_index(PTS, backend="brute")
    f = fused.query(QS, KnnSpec(5))
    _same(f, host.query(QS, KnnSpec(5)))
    _same(f, brute.query(QS, KnnSpec(5)))


def test_fused_explicit_start_radius_identity():
    fused, host = _pair()
    spec = KnnSpec(3, start_radius=2.0)
    _same(fused.query(QS, spec), host.query(QS, spec))


# ------------------------------------------------- the 1-dispatch contract


def test_fused_multi_round_is_one_dispatch():
    """The tentpole's counter proof: a multi-round search is ONE device
    program launch whatever the round count — 2 rounds and 8 rounds both
    cost exactly one dispatch (the host loop pays one per round plus the
    tail)."""
    D = get_metric("l2").pairwise(QS[:20], PTS)
    r_top = float(np.sort(D, 1)[:, 4].max()) * 1.05
    for r0, want_rounds in ((r_top / 2, 2), (r_top / 128, 8)):
        fused = build_index(PTS, backend="trueknn")
        before = fused.stats()["dispatches"]
        res = fused.query(QS[:20], KnnSpec(5, start_radius=r0))
        assert res.n_rounds == want_rounds
        assert fused.stats()["dispatches"] - before == 1
        assert res.timings["fused_dispatches"] == 1

        host = build_index(PTS, backend="trueknn", fused=False)
        before = host.stats()["dispatches"]
        hres = host.query(QS[:20], KnnSpec(5, start_radius=r0))
        _same(res, hres)
        assert host.stats()["dispatches"] - before >= want_rounds


def test_fused_plan_tag_and_stats_surface():
    fused, host = _pair()
    res = fused.query(QS, KnnSpec(4))
    assert res.timings["plan"].startswith("fused/rounds<=")
    assert fused.stats()["fused"] is True
    assert host.stats()["fused"] is False
    assert "fused" not in host.query(QS, KnnSpec(4)).timings.get("plan", "")
    tag = fused.prepare(KnnSpec(4)).explain()["tag"]
    assert tag.startswith("fused/rounds<=")


def test_fused_resolved_radius_p50_reported():
    fused, host = _pair()
    f = fused.query(QS, KnnSpec(5))
    h = host.query(QS, KnnSpec(5))
    assert f.timings["resolved_radius_p50"] > 0
    assert h.timings["resolved_radius_p50"] > 0


# ------------------------------------------------ spans and named scopes


def test_fused_answers_identical_with_spans_on():
    """Spans are host annotations only: the same searches with spans on
    and off give bit-identical answers and schedules."""
    from repro import trace

    off = build_index(PTS, backend="trueknn")
    on = build_index(PTS, backend="trueknn")
    want = [off.query(QS, KnnSpec(5)) for _ in range(2)]
    trace.enable(True)
    try:
        got = [on.query(QS, KnnSpec(5)) for _ in range(2)]
    finally:
        trace.enable(False)
    for a, b in zip(want, got):
        _same(a, b)
        assert [r.radius for r in a.rounds] == [r.radius for r in b.rounds]


def test_fused_program_is_jit_run_with_named_scopes(monkeypatch):
    """The device trace finds the fused program by its jit name and its
    rounds and tail by named scope."""
    from repro.core import fused_loop

    calls = []
    real = fused_loop._fused_fn

    def spy(*key):
        fn = real(*key)

        def call(*args):
            calls.append((fn, args))
            return fn(*args)

        return call

    monkeypatch.setattr(fused_loop, "_fused_fn", spy)
    res = build_index(PTS, backend="trueknn").query(
        QS, KnnSpec(5, start_radius=1e-3))
    assert res.n_rounds >= 2
    ((fn, args),) = calls
    text = fn.lower(*args).as_text(debug_info=True)
    assert "module @jit_run " in text
    for scope in ("trueknn.fused", "trueknn.round.b0", "trueknn.round.b1",
                  "trueknn.tail"):
        assert f"/{scope}/" in text, scope


def test_grid_build_seconds_count_grid_builds_only():
    """A fused search reports the time of the grids it built, not of its
    whole schedule; a search that builds none reports 0.0.  Its rounds'
    seconds are 0.0 (their device time is in the trace)."""
    index = build_index(PTS, backend="trueknn")
    cold = index.query(QS, KnnSpec(5))
    assert cold.timings["grid_build_seconds"] > 0.0
    index.query(QS, KnnSpec(5))
    before = index.stats()["grid_builds"]
    warm = index.query(QS, KnnSpec(5))
    assert index.stats()["grid_builds"] == before
    assert warm.timings["grid_build_seconds"] == 0.0
    assert all(r.seconds == 0.0 for r in warm.rounds)


def test_lattice_step_is_read_from_stats_and_the_span():
    index = build_index(PTS, backend="trueknn")
    s = index.stats()
    assert s["lattice_anchor"] is None and s["warm_lattice_step"] is None
    assert index._span_args() == {"search": 0}
    index.query(QS, KnnSpec(5))
    s = index.stats()
    step = s["warm_lattice_step"]
    assert isinstance(step, int)
    assert index._span_args() == {"search": 1, "start_step": step}
    res = index.query(QS, KnnSpec(5))
    assert res.timings["start_radius_source"] == "warm"
    assert res.start_radius == s["lattice_anchor"] * 2.0**step


def test_grid_probe_cache_memoizes_table_sizing():
    """The table-sizing probe memoizes per (point cloud, initial res): a
    rebuild at a probed resolution skips the O(N) host probe, and the
    trueknn backend surfaces the counters in stats()."""
    from repro.core.grid import build_grid

    cache = {}
    g1 = build_grid(PTS, 0.05, probe_cache=cache)
    assert cache["_misses"] == 1 and cache.get("_hits", 0) == 0
    g2 = build_grid(PTS, 0.05, probe_cache=cache)  # same res -> memo hit
    assert cache["_hits"] == 1 and cache["_misses"] == 1
    assert g1.table_size == g2.table_size and g1.cap == g2.cap
    build_grid(PTS, 0.8, probe_cache=cache)  # new res -> probe again
    assert cache["_misses"] == 2

    fused = build_index(PTS, backend="trueknn")
    fused.query(QS, KnnSpec(5))
    s = fused.stats()
    assert s["grid_probe_misses"] > 0  # schedule grids went through it
    assert s["grid_probe_hits"] >= 0
    # warm batches reuse whole cached grids: no new probes at all
    fused.query(QS + np.float32(0.001), KnnSpec(5))
    assert fused.stats()["grid_probe_misses"] == s["grid_probe_misses"]


def test_server_buckets_report_resolved_radius_p50():
    """The fused loop's resolved radii surface in the serving bucket
    stats (median of per-batch medians) with no extra device sync — they
    ride the result timings the backend already reports."""
    from repro.api import NeighborServer

    srv = NeighborServer(build_index(PTS, backend="trueknn"), max_batch=64)
    srv.submit(QS, KnnSpec(5)).result()
    buckets = srv.stats()["buckets"]
    vals = [b["resolved_radius_p50"] for b in buckets.values()]
    assert any(v is not None and v > 0 for v in vals)


def test_placed_fused_multi_round_is_one_dispatch():
    """The sharded fabric's tier of the same proof: a placed kNN batch
    whose shared-cut schedule takes many rounds is ONE fused mesh
    dispatch, bit-identical to host placement."""
    placed = build_index(
        PTS, backend="sharded", n_shards=5, placement="devices"
    )
    host = build_index(
        PTS, backend="sharded", n_shards=5, placement="host"
    )
    p = placed.query(QS, KnnSpec(5))
    h = host.query(QS, KnnSpec(5))
    _same(p, h)
    assert p.n_rounds >= 2
    assert p.timings["fused_dispatches"] == 1
    assert "/placed=1" in p.timings["plan"]


def test_grid_over_budget_folds_table_not_cells():
    """Past the bucket budget the hash table folds (collisions are
    filtered exactly) instead of coarsening the cells, and a round on the
    folded grid finds exactly what the unfolded one finds."""
    from repro.core.fixed_radius import fixed_radius_round
    from repro.core.grid import build_grid

    pts = make_dataset("kitti", 4000, seed=2)
    full = build_grid(pts, 0.5)
    folded = build_grid(
        pts, 0.5, max_bucket_elems=full.table_size * full.cap // 8
    )
    assert folded.res == full.res
    assert folded.table_size < full.table_size
    q, qid = pts[:200], np.arange(200, dtype=np.int32)
    a = fixed_radius_round(full, q, qid, 0.5, 6)
    b = fixed_radius_round(folded, q, qid, 0.5, 6)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[2], b[2])


def test_round_chunk_bounds_candidates_per_chunk():
    from repro.core.fixed_radius import CHUNK_CANDIDATES, round_chunk

    for d, cap in ((2, 8), (3, 32), (3, 512), (2, 1 << 16), (3, 1 << 20)):
        c = round_chunk(2048, d, cap)
        assert c >= 1 and c & (c - 1) == 0
        assert c == 1 or c * 3**d * cap <= CHUNK_CANDIDATES


def test_schedule_ends_where_a_round_cannot_beat_brute():
    """A round that gathers more slots per query than a chunk holds and
    than the cloud has points ends the schedule (the exact tail takes
    over) — except under a plain stop_radius, whose partial tails need
    the rounds."""
    from repro.core.fixed_radius import CHUNK_CANDIDATES

    idx = build_index(PTS, backend="trueknn")

    class G:
        cap = 1

    g = G()
    g.cap = CHUNK_CANDIDATES  # 9 * cap slots: past the budget and N
    assert idx._grid_no_better_than_brute(g, None, False)
    assert idx._grid_no_better_than_brute(g, 1.0, True)
    assert not idx._grid_no_better_than_brute(g, 1.0, False)
    g.cap = 64  # 576 slots >= N=500, but a chunk still holds them
    assert not idx._grid_no_better_than_brute(g, None, False)
