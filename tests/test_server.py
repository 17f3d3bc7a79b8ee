"""Tests for the NeighborServer front-end and the serve-loop/planner
bugfix sweep that rode along with it:

* served results are exactly what ``index.query`` returns, across
  interleaved specs and metrics (knn / hybrid / range, l2 / l1);
* pending requests coalesce into one padded microbatch (asserted through
  the batch-size stats, per the acceptance criteria);
* cache hits are exact copies and the quantization caveat is real;
* stats counters reconcile with what was submitted;
* ``KnnSpec(stop_radius=...)`` on the distributed backend takes the
  planner's companion-trueknn fallback instead of raising, and matches
  the trueknn oracle;
* ``warm_default_radius`` stays finite under stop_radius-truncated warm
  batches; ``dropped_counts`` counts queries, not inf cells;
* the distributed path meters candidate tests and ``_default_mesh`` warns
  when it drops devices to the power-of-2 prefix.
"""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.api import (
    AdmissionError,
    HybridSpec,
    KnnSpec,
    NeighborServer,
    RangeSpec,
    build_index,
    dropped_counts,
    warm_default_radius,
)
from repro.core import make_dataset

PTS = make_dataset("porto", 1200, seed=4)
QS = make_dataset("porto", 48, seed=11)
RADIUS = 0.5


# ------------------------------------------------ served == direct query


def test_server_matches_direct_interleaved_specs_and_metrics():
    index = build_index(PTS, backend="brute")
    direct = {
        ("knn", "l2"): index.query(QS, KnnSpec(5)),
        ("hyb", "l2"): index.query(QS, HybridSpec(5, RADIUS)),
        ("knn", "l1"): index.query(QS, KnnSpec(5), metric="l1"),
    }
    server = NeighborServer(build_index(PTS, backend="brute"))
    # interleaved submission order, split across requests
    t1 = server.submit(QS[:20], KnnSpec(5))
    t2 = server.submit(QS, HybridSpec(5, RADIUS))
    t3 = server.submit(QS[:16], KnnSpec(5), metric="l1")
    t4 = server.submit(QS[20:], KnnSpec(5))
    t5 = server.submit(QS[16:], KnnSpec(5), metric="l1")

    got_knn_d = np.vstack([t1.result().dists, t4.result().dists])
    got_knn_i = np.vstack([t1.result().idxs, t4.result().idxs])
    assert np.array_equal(got_knn_d, direct[("knn", "l2")].dists)
    assert np.array_equal(got_knn_i, direct[("knn", "l2")].idxs)

    hyb = t2.result()
    assert np.array_equal(hyb.dists, direct[("hyb", "l2")].dists)
    assert np.array_equal(hyb.idxs, direct[("hyb", "l2")].idxs)
    assert np.array_equal(hyb.found, direct[("hyb", "l2")].found)
    assert hyb.metric == "l2" and hyb.backend == "brute"

    got_l1_d = np.vstack([t3.result().dists, t5.result().dists])
    assert np.array_equal(got_l1_d, direct[("knn", "l1")].dists)
    assert t3.result().metric == "l1"


def test_server_range_spec_csr_matches_direct():
    index = build_index(PTS, backend="trueknn")
    spec = RangeSpec(RADIUS, max_neighbors=8)
    direct = index.query(QS, spec)
    server = NeighborServer(build_index(PTS, backend="trueknn"))
    ta = server.submit(QS[:30], spec)
    tb = server.submit(QS[30:], spec)
    ra, rb = ta.result(), tb.result()
    assert np.array_equal(
        np.concatenate([ra.dists, rb.dists]), direct.dists
    )
    assert np.array_equal(np.concatenate([ra.idxs, rb.idxs]), direct.idxs)
    assert np.array_equal(
        np.concatenate([ra.counts, rb.counts]), direct.counts
    )
    assert np.array_equal(
        np.concatenate([ra.truncated, rb.truncated]), direct.truncated
    )
    assert ra.radius == direct.radius
    # each row of a range answer stays nearest-first through reassembly
    for i in range(ra.n_queries):
        _, d = ra.neighbors(i)
        assert np.all(np.diff(d) >= 0)


def test_server_single_row_submit_and_worker_thread():
    index = build_index(PTS, backend="brute")
    direct = index.query(QS[:16], KnnSpec(4))
    server = NeighborServer(index)
    server.start()
    try:
        tickets = [server.submit(QS[i], KnnSpec(4)) for i in range(16)]
        outs = [t.result(timeout=60) for t in tickets]
    finally:
        server.stop()
    got = np.vstack([o.dists for o in outs])
    assert np.array_equal(got, direct.dists)
    assert all(o.dists.shape == (1, 4) for o in outs)


# ------------------------------------------------------- microbatching


def test_server_coalesces_pending_requests_into_one_batch():
    server = NeighborServer(build_index(PTS, backend="brute"))
    tickets = [server.submit(QS[i], KnnSpec(3)) for i in range(6)]
    # nothing served yet: no worker is running
    assert not any(t.done() for t in tickets)
    assert server.stats()["pending_rows"] == 6
    res = tickets[0].result()  # drives the queue inline
    # all six pending rows were coalesced into ONE padded batch
    assert res.timings["server_batch_rows"] == 6
    assert all(t.done() for t in tickets)
    bucket = server.stats()["buckets"]["default/knn/k=3/l2"]
    assert bucket["batches"] == 1
    assert bucket["batch_size_hist"] == {6: 1}
    assert bucket["mean_batch_rows"] >= 2  # the acceptance bar


def test_server_batches_only_merge_identical_specs():
    server = NeighborServer(build_index(PTS, backend="brute"), cache_size=0)
    a = server.submit(QS[:4], KnnSpec(3))
    b = server.submit(QS[:4], KnnSpec(4))  # different k: separate queue
    server.drain()
    assert a.result().dists.shape == (4, 3)
    assert b.result().dists.shape == (4, 4)
    buckets = server.stats()["buckets"]
    assert buckets["default/knn/k=3/l2"]["batches"] == 1
    assert buckets["default/knn/k=4/l2"]["batches"] == 1


def test_step_serves_oldest_head_first_no_starvation():
    """Scheduling is FIFO across buckets: a lone request in a minority
    bucket is served before younger arrivals in a busier bucket."""
    server = NeighborServer(build_index(PTS, backend="brute"), cache_size=0)
    old = server.submit(QS[:1], KnnSpec(3))
    time.sleep(0.005)  # make arrival order unambiguous
    young = [server.submit(QS[i], KnnSpec(4)) for i in range(5)]
    server.step()  # one microbatch: must pick the oldest head, not deepest
    assert old.done()
    assert not any(t.done() for t in young)
    server.drain()
    assert all(t.done() for t in young)


def test_server_max_batch_splits_oversized_queues():
    server = NeighborServer(
        build_index(PTS, backend="brute"), max_batch=16, cache_size=0
    )
    t = server.submit(QS, KnnSpec(3))  # 48 rows > max_batch
    res = t.result()
    assert res.dists.shape == (48, 3)
    bucket = server.stats()["buckets"]["default/knn/k=3/l2"]
    assert bucket["batches"] == 3
    assert all(size <= 16 for size in bucket["batch_size_hist"])


def test_result_recovers_when_worker_dies_without_draining():
    """A waiter blocked on a live worker must not hang forever if that
    worker exits without serving the queue (stop(drain=False) race): the
    sliced wait re-checks and falls back to driving the queue itself."""
    server = NeighborServer(build_index(PTS, backend="brute"), cache_size=0)
    t = server.submit(QS[:2], KnnSpec(3))
    dummy = threading.Thread(target=time.sleep, args=(0.3,))
    dummy.start()
    server._worker = dummy  # looks alive, will die having served nothing
    res = t.result(timeout=30)
    assert res.dists.shape == (2, 3)
    dummy.join()
    server._worker = None


def test_server_failed_batch_fails_tickets_instead_of_hanging():
    server = NeighborServer(build_index(PTS, backend="trueknn"))
    t = server.submit(QS[:4], KnnSpec(len(PTS) + 10))  # k > N: query raises
    with pytest.raises(AssertionError):
        t.result(timeout=30)
    assert t.done()
    assert server.stats()["pending_rows"] == 0
    # the server keeps serving after a failed batch
    ok = server.submit(QS[:4], KnnSpec(3)).result()
    assert ok.dists.shape == (4, 3)


def test_server_submit_validation():
    server = NeighborServer(build_index(PTS, backend="brute"))
    with pytest.raises(TypeError, match="QuerySpec"):
        server.submit(QS, 5)
    with pytest.raises(ValueError, match="queries must be"):
        server.submit(np.zeros((3, 7), np.float32), KnnSpec(2))
    with pytest.raises(ValueError, match="empty"):
        server.submit(np.zeros((0, PTS.shape[1]), np.float32), KnnSpec(2))


# --------------------------------------------------------------- cache


def test_server_cache_hits_are_exact_and_quantized():
    server = NeighborServer(build_index(PTS, backend="brute"))
    first = server.submit(QS[:8], KnnSpec(5)).result()
    assert first.timings["plan"] != "cache"
    again = server.submit(QS[:8], KnnSpec(5))
    assert again.done()  # pure cache hit: served at submit time
    res = again.result()
    assert res.timings["plan"] == "cache"
    assert res.timings["server_cache_hits"] == 8
    assert np.array_equal(res.dists, first.dists)
    assert np.array_equal(res.idxs, first.idxs)
    # sub-quantum perturbation collides onto the same cached row (the
    # documented quantization caveat)
    nudged = QS[:1] + np.float32(server.cache_quant * 0.25)
    hit = server.submit(nudged, KnnSpec(5)).result()
    assert hit.timings["plan"] == "cache"
    assert np.array_equal(hit.dists, first.dists[:1])
    # different spec or metric never hits
    miss = server.submit(QS[:1], KnnSpec(5), metric="l1").result()
    assert miss.timings["plan"] != "cache"


def test_server_cache_disabled_and_lru_bound():
    server = NeighborServer(build_index(PTS, backend="brute"), cache_size=0)
    server.submit(QS[:4], KnnSpec(3)).result()
    r = server.submit(QS[:4], KnnSpec(3)).result()
    assert r.timings["plan"] != "cache"
    assert server.stats()["cache"]["rows"] == 0

    small = NeighborServer(build_index(PTS, backend="brute"), cache_size=8)
    small.submit(QS[:32], KnnSpec(3)).result()
    assert small.stats()["cache"]["rows"] == 8  # LRU bound respected


# --------------------------------------------------------------- stats


def test_server_stats_reconcile_with_submissions():
    server = NeighborServer(build_index(PTS, backend="brute"), cache_size=0)
    reqs = [
        (QS[:10], KnnSpec(4), "l2"),
        (QS[10:25], KnnSpec(4), "l2"),
        (QS[:6], HybridSpec(4, RADIUS), "l2"),
        (QS[:5], KnnSpec(4), "l1"),
    ]
    tickets = [server.submit(q, s, metric=m) for q, s, m in reqs]
    served_rows = server.drain()
    for t in tickets:
        assert t.done()
    s = server.stats()
    assert s["submitted"] == s["served"] == len(reqs)
    assert s["pending_rows"] == 0
    assert served_rows == sum(len(q) for q, _, _ in reqs)
    assert sum(b["requests"] for b in s["buckets"].values()) == len(reqs)
    assert sum(b["rows"] for b in s["buckets"].values()) == served_rows
    assert s["cache"]["misses"] == served_rows
    knn_l2 = s["buckets"]["default/knn/k=4/l2"]
    assert knn_l2["requests"] == 2 and knn_l2["rows"] == 25
    assert knn_l2["latency_p50_ms"] is not None
    assert knn_l2["latency_p99_ms"] >= knn_l2["latency_p50_ms"]
    assert knn_l2["queue_depth"] == 0


# ------------------------- planner fallback: distributed + stop_radius


def test_distributed_stop_radius_takes_planner_fallback():
    pts = make_dataset("porto", 900, seed=7)
    qs = make_dataset("porto", 40, seed=13)
    k = 5
    oracle = build_index(pts, backend="trueknn")
    want = oracle.query(qs, KnnSpec(k, stop_radius=0.3))

    index = build_index(pts, backend="distributed")
    assert (
        index.prepare(KnnSpec(k, stop_radius=0.3)).explain()["route"]
        == "knn_fallback"
    )
    res = index.query(qs, KnnSpec(k, stop_radius=0.3))  # must not raise
    assert res.backend == "distributed"
    # the companion-trueknn fallback answers with the full stop_radius
    # semantics: identical to a fresh trueknn index over the same cloud
    assert np.array_equal(res.dists, want.dists)
    assert np.array_equal(res.idxs, want.idxs)
    assert np.array_equal(res.found, want.found)
    # tail semantics survived: some queries kept partial (< k) lists
    assert (res.found < k).any() and (res.found >= k).any()
    # the companion view is cached across calls
    view = index._knn_fallback_view
    index.query(qs, KnnSpec(k, stop_radius=0.3))
    assert index._knn_fallback_view is view


def test_distributed_plain_knn_still_native():
    pts = make_dataset("porto", 600, seed=9)
    index = build_index(pts, backend="distributed")
    assert index.prepare(KnnSpec(4)).explain()["route"] == "native"
    res = index.query(pts[:32], KnnSpec(4))
    assert "plan" not in res.timings  # native path, no fallback tag


# ------------------------------------ warm radius + dropped counters


def test_warm_default_radius_finite_under_truncated_warm_batch():
    pts = make_dataset("porto", 900, seed=3)
    qs = make_dataset("porto", 64, seed=21)
    index = build_index(pts, backend="trueknn")
    # stop_radius chosen so some warm queries cannot fill k: their last
    # column is inf, which used to push the median default radius to inf
    warm = index.query(qs, KnnSpec(5, stop_radius=0.05))
    assert np.isinf(warm.dists[:, -1]).any()
    r = warm_default_radius(warm.dists, index)
    assert np.isfinite(r) and r > 0
    fin = warm.dists[:, -1][np.isfinite(warm.dists[:, -1])]
    assert r == pytest.approx(float(np.median(fin.astype(np.float64))))
    # the finite radius builds a valid spec (inf/nan would raise here)
    HybridSpec(5, r)


def test_warm_default_radius_all_inf_falls_back_to_sampled():
    from repro.core import sample_start_radius

    pts = make_dataset("uniform", 500, seed=2)
    index = build_index(pts, backend="trueknn")
    all_inf = np.full((16, 4), np.inf, np.float32)
    # fresh index: nothing sampled yet, the helper samples the cloud itself
    r = warm_default_radius(all_inf, index)
    assert np.isfinite(r) and r > 0
    assert r == pytest.approx(float(sample_start_radius(pts)))
    # once the index has its own Alg.-2 sample, that value is reused
    index.query(pts[:32], KnnSpec(3))
    assert index._sampled_r is not None
    r2 = warm_default_radius(all_inf, index)
    assert r2 == pytest.approx(float(index._sampled_r))
    with pytest.raises(ValueError, match="sampled radius"):
        warm_default_radius(all_inf)  # no index to fall back to


def test_dropped_counts_are_per_query_not_per_cell():
    dists = np.array(
        [
            [0.1, 0.2, 0.3],     # full row: not dropped
            [0.1, np.inf, np.inf],  # partial: 2 inf cells, ONE query
            [np.inf, np.inf, np.inf],  # empty: 3 inf cells, ONE query
        ],
        np.float32,
    )
    partial, empty = dropped_counts(dists)
    assert (partial, empty) == (2, 1)
    assert int(np.isinf(dists).sum()) == 5  # the old counter overstated


# --------------------------------------- distributed work metering


def test_distributed_index_meters_candidate_tests():
    pts = make_dataset("uniform", 512, seed=5)
    index = build_index(pts, backend="distributed")
    res = index.query(pts[:64], KnnSpec(4))
    # dense sharded engine: every padded query row tests every point, so
    # at least one full pass over the cloud is metered
    assert res.n_tests >= 64 * 512
    assert index.stats()["total_tests"] == res.n_tests
    res2 = index.query(pts[64:128], KnnSpec(4))
    assert index.stats()["total_tests"] == res.n_tests + res2.n_tests


def test_default_mesh_warns_when_dropping_devices():
    """6 host devices -> the pow2 prefix keeps 4 and must say so."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=6"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [
            sys.executable,
            "-c",
            """
import warnings
from repro.api.backends.distributed import _default_mesh

with warnings.catch_warnings(record=True) as w:
    warnings.simplefilter("always")
    mesh = _default_mesh("model")
hit = [x for x in w if "4 of 6" in str(x.message)]
print("SHAPE", dict(mesh.shape), "WARNED", len(hit) == 1)
""",
        ],
        capture_output=True,
        text=True,
        timeout=560,
        env=env,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    assert "SHAPE {'model': 4} WARNED True" in out.stdout


# ------------------------------ multi-tenancy, reordering, admission


def test_server_multi_tenant_routes_by_index_name():
    pts_b = make_dataset("kitti", 700, seed=8)  # different dim than PTS
    qs_b = make_dataset("kitti", 24, seed=15)
    ia = build_index(PTS, backend="brute")
    ib = build_index(pts_b, backend="brute")
    server = NeighborServer(indexes={"gps": ia, "lidar": ib}, cache_size=0)
    ta = server.submit(QS, KnnSpec(4), index="gps")
    tb = server.submit(qs_b, KnnSpec(4), index="lidar")
    assert np.array_equal(ta.result().dists, ia.query(QS, KnnSpec(4)).dists)
    assert np.array_equal(
        tb.result().dists, ib.query(qs_b, KnnSpec(4)).dists
    )
    s = server.stats()
    assert set(s["buckets"]) == {"gps/knn/k=4/l2", "lidar/knn/k=4/l2"}
    assert set(s["indexes"]) == {"gps", "lidar"}
    # rows are validated against the *named* tenant's dimensionality
    with pytest.raises(ValueError, match="for index 'lidar'"):
        server.submit(QS, KnnSpec(3), index="lidar")
    with pytest.raises(KeyError, match="unknown index"):
        server.submit(QS, KnnSpec(3), index="nope")
    # several tenants and no name: ambiguous
    with pytest.raises(ValueError, match="pass submit"):
        server.submit(QS, KnnSpec(3))
    # a sole non-default tenant resolves without a name
    solo = NeighborServer(indexes={"only": ia}, cache_size=0)
    assert solo.submit(QS, KnnSpec(3)).result().dists.shape == (48, 3)


def test_server_add_remove_index_lifecycle():
    ia = build_index(PTS, backend="brute")
    server = NeighborServer(ia, cache_size=0)
    server.add_index("extra", build_index(PTS, backend="brute"))
    with pytest.raises(ValueError, match="already registered"):
        server.add_index("extra", ia)
    t = server.submit(QS[:4], KnnSpec(3), index="extra")
    with pytest.raises(ValueError, match="pending"):
        server.remove_index("extra")  # in-flight rows: refuse
    t.result()
    server.remove_index("extra")
    with pytest.raises(KeyError):
        server.remove_index("extra")
    # default tenant still serves and the back-compat handle points at it
    assert server.index is ia
    assert server.submit(QS[:2], KnnSpec(2)).result().dists.shape == (2, 2)


def test_server_tenants_do_not_share_cache_entries():
    ia = build_index(PTS, backend="brute")
    ib = build_index(PTS, backend="brute")  # same cloud, different tenant
    server = NeighborServer(indexes={"a": ia, "b": ib})
    first = server.submit(QS[:4], KnnSpec(3), index="a")
    first.result()
    hit = server.submit(QS[:4], KnnSpec(3), index="a")
    assert hit.result().timings["plan"] == "cache"
    miss = server.submit(QS[:4], KnnSpec(3), index="b")
    assert miss.result().timings["plan"] != "cache"


def test_server_morton_reorder_preserves_results_and_counts():
    index = build_index(PTS, backend="brute")
    direct = index.query(QS, KnnSpec(5))
    # adversarial submission order: interleave far-apart rows
    perm = np.argsort(np.tile([0, 1], len(QS) // 2 + 1)[: len(QS)],
                      kind="stable")
    scrambled = QS[perm]
    server = NeighborServer(build_index(PTS, backend="brute"), cache_size=0)
    res = server.submit(scrambled, KnnSpec(5)).result()
    # unsort restores request row order exactly
    assert np.array_equal(res.dists, direct.dists[perm])
    assert np.array_equal(res.idxs, direct.idxs[perm])
    s = server.stats()
    assert s["reordered_batches"] == 1  # the satellite's proof-of-engagement
    assert s["buckets"]["default/knn/k=5/l2"]["reordered_batches"] == 1
    # reorder="none" serves identically but never reorders
    off = NeighborServer(build_index(PTS, backend="brute"),
                         cache_size=0, reorder="none")
    res2 = off.submit(scrambled, KnnSpec(5)).result()
    assert np.array_equal(res2.dists, res.dists)
    assert off.stats()["reordered_batches"] == 0
    with pytest.raises(ValueError, match="reorder"):
        NeighborServer(index, reorder="hilbert")


def test_server_admission_control_rejects_past_max_queue():
    server = NeighborServer(
        build_index(PTS, backend="brute"), cache_size=0, max_queue=10
    )
    ok = server.submit(QS[:8], KnnSpec(3))
    shed = server.submit(QS[:8], KnnSpec(3))  # 8 pending + 8 > 10
    assert shed.done()  # fast-failing ticket: no waiting, no queueing
    with pytest.raises(AdmissionError, match="queue full"):
        shed.result()
    s = server.stats()
    assert s["rejected"] == 1
    assert s["buckets"]["default/knn/k=3/l2"]["rejected"] == 1
    # shed requests never entered the queue or the request meters
    assert s["pending_rows"] == 8
    assert s["buckets"]["default/knn/k=3/l2"]["requests"] == 1
    assert np.array_equal(
        ok.result().dists,
        build_index(PTS, backend="brute").query(QS[:8], KnnSpec(3)).dists,
    )
    # queue drained: admissions resume
    assert server.submit(QS[:8], KnnSpec(3)).result().dists.shape == (8, 3)
    assert server.stats()["rejected"] == 1


def test_admission_control_serves_cached_rows_when_queue_full():
    """The cache is consulted before admission: a fully cached repeat
    query is served even when the queue is at its bound — only rows that
    would actually enqueue count against max_queue."""
    server = NeighborServer(
        build_index(PTS, backend="brute"), max_queue=8, cache_size=1024
    )
    primed = server.submit(QS[:4], KnnSpec(3))
    primed.result()  # queue drained, answers cached
    blocker = server.submit(QS[8:16], KnnSpec(3))  # fills the queue: 8 of 8
    cached = server.submit(QS[:4], KnnSpec(3))  # 0 uncached rows: admitted
    assert cached.done()
    res = cached.result()
    assert res.timings["plan"] == "cache"
    assert np.array_equal(res.dists, primed.result().dists)
    shed = server.submit(QS[16:20], KnnSpec(3))  # uncached rows: shed
    with pytest.raises(AdmissionError, match="queue full"):
        shed.result()
    assert server.stats()["rejected"] == 1
    blocker.result()


def test_remove_index_refuses_while_batch_is_in_flight():
    """Rows popped into a batch the server is executing still count as
    pending: remove_index must refuse mid-batch, not yank the tenant out
    from under its own query call."""
    idx = build_index(PTS, backend="brute")
    server = NeighborServer(indexes={"x": idx}, cache_size=0)
    orig = idx.execute_knn  # hook the engine: both query and prepared
    seen = {}               # plans pass through it mid-batch

    def knn_and_try_remove(q, spec, metric, ctx=None):
        with pytest.raises(ValueError, match="pending"):
            server.remove_index("x")
        seen["guarded"] = True
        return orig(q, spec, metric, ctx=ctx)

    idx.execute_knn = knn_and_try_remove
    res = server.submit(QS[:4], KnnSpec(3), index="x").result()
    assert seen["guarded"] and res.dists.shape == (4, 3)
    server.remove_index("x")  # drained: removal succeeds


def test_admission_control_counts_in_flight_rows_as_pending():
    """A popped batch still executing counts against max_queue — the same
    pending accounting remove_index uses — so a slow batch can't open the
    gate to another max_batch of rows."""
    idx = build_index(PTS, backend="brute")
    server = NeighborServer(idx, cache_size=0, max_queue=8)
    orig = idx.execute_knn  # hook the engine: both query and prepared
    seen = {}               # plans pass through it mid-batch

    def knn_and_probe(q, spec, metric, ctx=None):
        # mid-batch: 8 rows in flight, queue empty — a 4-row submit must
        # still be shed (8 + 4 > 8)
        shed = server.submit(QS[8:12], KnnSpec(3))
        assert shed.done()
        with pytest.raises(AdmissionError, match="8 rows pending"):
            shed.result()
        seen["probed"] = True
        return orig(q, spec, metric, ctx=ctx)

    idx.execute_knn = knn_and_probe
    ok = server.submit(QS[:8], KnnSpec(3))
    res = ok.result()
    idx.execute_knn = orig
    assert seen["probed"] and res.dists.shape == (8, 3)
    assert server.stats()["rejected"] == 1
    # batch done: admissions resume
    assert server.submit(QS[:4], KnnSpec(3)).result().dists.shape == (4, 3)


def test_multi_tenant_index_property_is_loud_not_attributeerror():
    """hasattr/getattr-with-default must not swallow the ambiguity error."""
    server = NeighborServer(
        indexes={
            "a": build_index(PTS, backend="brute"),
            "b": build_index(PTS, backend="brute"),
        }
    )
    with pytest.raises(ValueError, match="several indexes"):
        server.index
    # even hasattr/getattr-with-default stay loud (they swallow only
    # AttributeError, which the property deliberately never raises)
    with pytest.raises(ValueError, match="several indexes"):
        hasattr(server, "index")


def test_poisson_open_loop_survives_shed_requests():
    """Under the overload max_queue exists for, the shared open-loop
    driver reports served results and drops shed tickets instead of
    crashing on the first AdmissionError."""
    from repro.api.server import poisson_open_loop

    server = NeighborServer(
        build_index(PTS, backend="brute"), cache_size=0, max_queue=0
    )
    rng = np.random.default_rng(0)
    results, wall, lat = poisson_open_loop(
        server, QS[:8], KnnSpec(3), rate=1e6, rng=rng
    )
    assert results == [] and lat.size == 0  # every request was shed
    assert server.stats()["rejected"] == 8
    assert not server.stats()["worker_running"]  # worker stopped cleanly


def test_serve_launcher_defaults_to_neighbor_search():
    from repro.launch import serve

    assert serve._parser().parse_args([]).mode == "knn"


def test_serve_launcher_refuses_devices_off_cpu(monkeypatch):
    """--devices forces CPU host devices; on any other backend it is
    refused, not silently ignored."""
    import jax

    from repro.launch import serve

    import repro.compile_cache

    monkeypatch.setenv("XLA_FLAGS", "")
    monkeypatch.setattr(repro.compile_cache, "enable_compile_cache",
                        lambda: "")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(SystemExit, match="--devices"):
        serve.main(["--devices", "2", "--n", "64", "--batches", "1"])
