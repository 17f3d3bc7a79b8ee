"""Distributed kNN: points sharded across the mesh, hypercube top-k merge.

Layout: points (N, d) sharded over the ``model`` axis; queries (Q, d) sharded
over the batch/FSDP axes.  Every device computes a fused streaming top-k of
its query slice against its point shard (the Pallas kernel), then the
per-shard candidate lists merge across the model axis with a log2(P)-step
hypercube exchange (``ppermute`` with XOR partners): top-k merge is
associative and commutative, so after log2 steps every shard holds the global
top-k — moving O(k·log P) candidates per query instead of O(k·P) for a naive
all-gather.

The multi-round TrueKNN driver composes on top: the paper's query-retirement
happens host-side between rounds (compaction), so later rounds move fewer
queries through the mesh — the distributed transplant of "don't relaunch
resolved rays".

:class:`PlacedFabric` is the second placement primitive in this file, built
for the ``sharded`` composite backend: instead of one cloud split evenly
over a pow2 ``model`` axis, it pins an arbitrary list of per-shard point
blocks to mesh devices (padded slot axis, masked empty slots — any device
count works) and answers one *fused* per-slot top-k/count dispatch per
call.  It deliberately has no merge network: per-slot candidate lists
gather back to the host, where the sharded backend's exact merge paths
(``topk_merge_rows`` / ``merge_range``) fold them with the same float
semantics as its sequential per-child path — the fabric only removes the
S-sequential-dispatch launch tax, never touches answer bits.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels.ops import pairwise_topk
from repro.kernels.ref import pairwise_topk_ref


def _merge_topk(d_a, i_a, d_b, i_b, k):
    d = jnp.concatenate([d_a, d_b], axis=1)
    i = jnp.concatenate([i_a, i_b], axis=1)
    neg, sel = jax.lax.top_k(-d, k)
    return -neg, jnp.take_along_axis(i, sel, axis=1)


def make_distributed_knn(
    mesh: Mesh,
    k: int,
    *,
    radius: float = np.inf,
    use_kernel: bool = True,
    point_axis: str = "model",
):
    """Returns fn(points, queries, query_ids) built on shard_map.

    points: (N, d) — sharded P(point_axis, None).
    queries: (Q, d) — sharded P(batch_axes, None).
    query_ids: (Q,) global point index of each query for self-exclusion
               (-1 = no exclusion) — sharded with queries.
    Returns (d2 (Q, k), idx (Q, k) global indices, counts (Q,)).
    """
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    p_size = mesh.shape[point_axis]
    assert p_size & (p_size - 1) == 0, "hypercube merge wants pow2 shards"

    def local_fn(pts_l, q_l, qid_l):
        n_local = pts_l.shape[0]
        n_global = n_local * p_size
        shard = jax.lax.axis_index(point_axis)
        qid_local = qid_l - shard * n_local  # out-of-shard ids never match
        if use_kernel:
            d2, idx, cnt = pairwise_topk(
                q_l, pts_l, k, radius=radius, query_ids=qid_local
            )
        else:
            r2 = np.float32(radius) ** 2 if np.isfinite(radius) else np.inf
            d2, idx, cnt = pairwise_topk_ref(
                q_l, pts_l, k, radius2=r2, query_ids=qid_local
            )
        idx = jnp.where(
            idx < n_local, idx + shard * n_local, n_global
        ).astype(jnp.int32)

        # hypercube merge over the point axis
        step = 1
        while step < p_size:
            perm = [(i, i ^ step) for i in range(p_size)]
            od2 = jax.lax.ppermute(d2, point_axis, perm)
            oidx = jax.lax.ppermute(idx, point_axis, perm)
            ocnt = jax.lax.ppermute(cnt, point_axis, perm)
            d2, idx = _merge_topk(d2, idx, od2, oidx, k)
            cnt = cnt + ocnt
            step *= 2
        return d2, idx, cnt

    qspec = P(batch_axes or None, None)
    return jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(point_axis, None), qspec, P(batch_axes or None)),
        out_specs=(qspec, qspec, P(batch_axes or None)),
        check_vma=False,
    )


def distributed_trueknn(
    points,
    k: int,
    mesh: Mesh,
    *,
    queries=None,
    start_radius=None,
    growth: float = 2.0,
    max_rounds: int = 32,
    use_kernel: bool = False,
    points_device=None,
):
    """Multi-round unbounded kNN over mesh-sharded points (host-orchestrated
    rounds, paper Alg. 3).  Query retirement compacts between rounds.

    Returns ``(dists, idxs, rounds, n_tests)``.  ``n_tests`` counts
    candidate distance evaluations (the paper's work metric): the dense
    streaming engine evaluates every (query, point) pair each round, so the
    count is exactly ``sum over rounds of padded_alive * N`` — padding rows
    included, since they are real work on the mesh.

    HONESTY NOTE (see DESIGN.md): with the dense streaming engine a single
    pass is already exact, so the multi-round structure only pays off when
    the per-round engine is radius-bounded and cheaper — i.e. with per-shard
    hash grids (the single-device path; its sharded-stack port is the
    §Perf extension).  This driver therefore converges in one round for
    radius=inf engines, and exists so the radius-bounded/grid engines slot
    in without changing the orchestration.
    """
    from repro.core.sampling import sample_start_radius

    pts = np.asarray(points, np.float32)
    n, d = pts.shape
    if queries is None:
        q_all = pts
        qid_all = np.arange(n, dtype=np.int32)
    else:
        q_all = np.asarray(queries, np.float32)
        qid_all = np.full((q_all.shape[0],), -1, np.int32)
    q_total = q_all.shape[0]
    r = float(start_radius) if start_radius else sample_start_radius(pts)

    out_d = np.full((q_total, k), np.inf, np.float32)
    out_i = np.full((q_total, k), n, np.int32)
    alive = np.arange(q_total)
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bsz = int(np.prod([mesh.shape[a] for a in batch_axes])) or 1

    # a resident caller (DistributedIndex) pre-places the shards once at
    # build; one-shot callers pay the transfer here
    if points_device is None:
        points_device = jax.device_put(pts, NamedSharding(mesh, P("model", None)))
    pts_j = points_device
    qsh = NamedSharding(mesh, P(batch_axes or None, None))
    idsh = NamedSharding(mesh, P(batch_axes or None))

    def run_round(q_sub, qid_sub, rad):
        m = q_sub.shape[0]
        m_pad = max(bsz, 1 << max(0, (m - 1).bit_length()))
        q = np.zeros((m_pad, d), np.float32)
        q[:m] = q_sub
        qid = np.full((m_pad,), -1, np.int32)
        qid[:m] = qid_sub
        fn = make_distributed_knn(mesh, k, radius=rad, use_kernel=use_kernel)
        d2, idx, cnt = jax.jit(fn)(
            pts_j, jax.device_put(q, qsh), jax.device_put(qid, idsh)
        )
        tests = m_pad * n  # dense engine: every padded row vs every point
        return np.asarray(d2)[:m], np.asarray(idx)[:m], np.asarray(cnt)[:m], tests

    rounds = 0
    n_tests = 0
    while alive.size and rounds < max_rounds:
        d2, idx, cnt, tests = run_round(q_all[alive], qid_all[alive], r)
        n_tests += tests
        resolved = cnt >= k
        done = alive[resolved]
        out_d[done] = d2[resolved]
        out_i[done] = idx[resolved]
        alive = alive[~resolved]
        r *= growth
        rounds += 1

    if alive.size:  # tail: one exact unbounded pass
        d2, idx, _, tests = run_round(q_all[alive], qid_all[alive], np.inf)
        n_tests += tests
        out_d[alive] = d2
        out_i[alive] = idx

    return np.sqrt(np.maximum(out_d, 0)), out_i, rounds, n_tests


# -- placed shard fabric ------------------------------------------------------

#: distance forms the fused slot dispatch can compute.  Each one replicates,
#: op for op, the float32 arithmetic of the engine the sharded backend's
#: sequential per-child path would have used for the same route, so host-side
#: folds stay bit-identical:
#:   sq_l2   — squared L2 via the diff form (``fixed_radius``/low-d brute);
#:             callers sqrt on the host (device sqrt rounds differently).
#:   l1      — |diff| summed with ``jnp.sum`` (the brute engine's knn form).
#:   l1_acc  — |diff| accumulated per axis in order (the Pallas kernel's
#:             range form; ``jnp.sum``'s reduce order differs at d >= 3).
#:   linf    — running max of |diff| (exact either way; one form suffices).
PLACED_FORMS = ("sq_l2", "l1", "l1_acc", "linf")


def _slot_form_dists(form: str, blk, q):
    """Raw-form (Qp, B) distances of one slot block against the query
    batch — THE arithmetic contract of the placed paths.  Both the
    per-round fused dispatch and the fused round loop call exactly this,
    so their candidate orders agree bit for bit with each other and with
    the host engine each form transcribes (see ``PLACED_FORMS``)."""
    B = blk.shape[0]
    if form == "sq_l2":
        diff = q[:, None, :] - blk[None, :, :]
        return jnp.sum(diff * diff, -1)
    if form == "l1":
        ad = jnp.abs(q[:, None, :] - blk[None, :, :])
        return jnp.sum(ad, axis=-1)
    if form == "linf":
        ad = jnp.abs(q[:, None, :] - blk[None, :, :])
        return jnp.max(ad, -1)
    # l1_acc: the kernel's per-axis accumulation order
    dist = jnp.zeros((q.shape[0], B), jnp.float32)
    for a in range(q.shape[1]):
        dist = dist + jnp.abs(q[:, a][:, None] - blk[:, a][None, :])
    return dist


class PlacedFabric:
    """Per-shard point blocks pinned to mesh devices, one fused dispatch.

    The sharded backend's scale seam made answers exact; this makes the
    fabric *parallel*: every shard's rows live as a zero-padded block in a
    (slots, block_rows, dim) array sharded over a 1-D mesh axis, and one
    ``shard_map`` call computes every slot's dense top-k (and in-radius
    count) against the whole query batch — visit masks and the radius
    threshold ride along as device-resident *data*, so a round is ONE
    XLA dispatch whatever the shard mix, and mixed visit patterns reuse
    the same compiled executable.

    Slot layout: ``n_slots`` is the shard count rounded UP to a multiple
    of the device count — a non-pow2 (or non-divisor) device count costs
    masked empty slots, never silently dropped devices (contrast the
    distributed backend's pow2-prefix mesh).  Hot shards can be *split*
    across free slots (:meth:`rebalance`): each slot owns a contiguous
    ascending-index row range of its shard, so the union of slot answers
    is exactly the shard answer and merges stay order-exact.

    The fabric is space-aware: metric routes that search a transformed
    cloud (cosine's normalize-then-L2) register the transform once via
    :meth:`add_space` and dispatch against lazily placed transformed
    blocks, mirroring the companion ``metric_view`` indexes of the
    sequential path.
    """

    def __init__(self, blocks, *, mesh: Mesh | None = None,
                 axis: str = "shard"):
        blocks = [np.ascontiguousarray(b, np.float32) for b in blocks]
        assert blocks, "PlacedFabric needs at least one shard block"
        self._axis = axis
        if mesh is None:
            mesh = Mesh(np.asarray(jax.devices()), (axis,))
        self.mesh = mesh
        self.n_devices = int(mesh.shape[axis])
        self._spaces = {"raw": blocks}  # name -> per-shard host blocks
        n_shards = len(blocks)
        d = self.n_devices
        # pad the slot axis to a device multiple: every device carries the
        # same number of slots, empty slots are fully masked
        self.n_slots = -(-n_shards // d) * d
        self.block_rows = max(max(b.shape[0] for b in blocks), 1)
        self.dim = blocks[0].shape[1]
        #: slot j -> (shard id, row lo, row hi) within that shard's block;
        #: (-1, 0, 0) marks an empty (padding or not-yet-used) slot
        self.slots = [(s, 0, blocks[s].shape[0]) for s in range(n_shards)]
        self.slots += [(-1, 0, 0)] * (self.n_slots - n_shards)
        self.dispatches = 0
        self.rebalances = 0
        self._dev_blocks: dict = {}  # space name -> placed (slots, B, dim)
        self._dev_nvalid = None

    # -- spaces ------------------------------------------------------------

    def add_space(self, name: str, transform) -> None:
        """Register a transformed search space (e.g. cosine's normalized
        cloud).  ``transform`` maps one host block (n, dim) -> (n, dim);
        applied per shard so transformed blocks match the sequential
        path's companion indexes row for row."""
        if name not in self._spaces:
            self._spaces[name] = [
                transform(b) if b.size else b for b in self._spaces["raw"]
            ]

    def has_space(self, name: str) -> bool:
        return name in self._spaces

    # -- placement ---------------------------------------------------------

    def _placed_nvalid(self):
        if self._dev_nvalid is None:
            nv = np.asarray([hi - lo for _, lo, hi in self.slots], np.int32)
            self._dev_nvalid = jax.device_put(
                nv, NamedSharding(self.mesh, P(self._axis))
            )
        return self._dev_nvalid

    def _placed_blocks(self, space: str):
        placed = self._dev_blocks.get(space)
        if placed is None:
            host = self._spaces[space]
            arr = np.zeros(
                (self.n_slots, self.block_rows, self.dim), np.float32
            )
            for j, (s, lo, hi) in enumerate(self.slots):
                if s >= 0 and hi > lo:
                    arr[j, : hi - lo] = host[s][lo:hi]
            placed = jax.device_put(
                arr, NamedSharding(self.mesh, P(self._axis, None, None))
            )
            self._dev_blocks[space] = placed
        return placed

    def _invalidate_placement(self) -> None:
        self._dev_blocks.clear()
        self._dev_nvalid = None

    # -- the fused dispatch ------------------------------------------------

    @functools.lru_cache(maxsize=None)  # noqa: B019 — lives with the fabric
    def _fused_fn(self, form: str, k: int):
        """Jitted shard_map round for (distance form, top-k width); query
        count buckets through jit's own shape cache, and the visit mask /
        threshold are traced data, so mixed shard cuts share executables."""
        assert form in PLACED_FORMS, form
        axis = self._axis
        B = self.block_rows

        def one_slot(blk, nv, vm, q, thr):
            # blk (B, dim) zero-padded rows; nv () valid-row count;
            # vm (Qp,) this slot's visit mask; q (Qp, dim); thr () f32
            dist = _slot_form_dists(form, blk, q)
            keep = (jnp.arange(B, dtype=jnp.int32)[None, :] < nv) & vm[:, None]
            dist = jnp.where(keep, dist, jnp.inf)
            cnt = jnp.sum((dist <= thr) & keep, axis=1, dtype=jnp.int32)
            kk = min(k, B)
            neg, idx = jax.lax.top_k(-dist, kk)
            d = -neg
            idx = jnp.where(jnp.isfinite(d), idx, B).astype(jnp.int32)
            if kk < k:
                d = jnp.concatenate(
                    [d, jnp.full((d.shape[0], k - kk), jnp.inf, d.dtype)], 1
                )
                idx = jnp.concatenate(
                    [idx, jnp.full((idx.shape[0], k - kk), B, jnp.int32)], 1
                )
            return d, idx, cnt

        def local(blocks, nvalid, vmask, q, thr):
            # per-device slice: blocks (g, B, dim), nvalid (g,), vmask (g, Qp)
            return jax.vmap(
                lambda b, n, v: one_slot(b, n, v, q, thr[0, 0])
            )(blocks, nvalid, vmask)

        fn = jax.shard_map(
            local,
            mesh=self.mesh,
            in_specs=(
                P(axis, None, None),
                P(axis),
                P(axis, None),
                P(None, None),
                P(None, None),
            ),
            out_specs=(P(axis, None, None), P(axis, None, None),
                       P(axis, None)),
            check_vma=False,
        )
        return jax.jit(fn)

    def topk(self, space: str, form: str, queries, visit_slots, k: int,
             threshold: float = np.inf):
        """One fused per-slot dispatch: dense top-k of every slot block
        against ``queries`` plus the per-(slot, query) count of candidates
        with ``dist <= threshold``.

        queries: (Qp, dim) float32.
        visit_slots: (n_slots, Qp) bool — False pairs contribute nothing
            (their slots still run; masking is data, not shape).
        Returns host arrays ``(d (slots, Qp, k) raw engine-form distances,
        idx (slots, Qp, k) slot-local rows — ``block_rows`` = no candidate,
        cnt (slots, Qp) int32)``.
        """
        q = np.ascontiguousarray(queries, np.float32)
        vm = np.ascontiguousarray(visit_slots, bool)
        assert vm.shape == (self.n_slots, q.shape[0]), vm.shape
        thr = np.asarray([[threshold]], np.float32)
        d, idx, cnt = self._fused_fn(form, int(k))(
            self._placed_blocks(space), self._placed_nvalid(), vm, q, thr
        )
        self.dispatches += 1
        return np.asarray(d), np.asarray(idx), np.asarray(cnt)

    # -- the fused round loop ----------------------------------------------

    @functools.lru_cache(maxsize=None)  # noqa: B019 — lives with the fabric
    def _fused_rounds_fn(self, form: str, k_eff: int, self_mode: bool,
                         max_rounds: int, sentinel: int):
        """Jitted shard_map program for the WHOLE shared-cut radius
        schedule: a ``lax.while_loop`` whose carry (candidate pool,
        unresolved mask, radius, resolution log) is replicated across the
        mesh, with only the per-slot block distances sharded — one device
        program per batch however many rounds the schedule takes.

        The slot layout (shard ids, valid counts, global-index lookups)
        and every schedule parameter (seed, growth, per-query floors and
        cover bounds) are *traced data*, so a rebalance — which moves rows
        between slots but never changes shapes — reuses the compiled
        executable.  The cache key is the static skeleton only."""
        assert form in ("sq_l2", "l1", "linf"), form
        axis = self._axis
        B = self.block_rows
        n_slots = self.n_slots
        kk = min(k_eff, B)

        def local(blocks, nvalid, shards, gmaps, q, sid, bounds, floors,
                  cover, alive0, params):
            # blocks (g, B, dim) / nvalid (g,) / shards (g,) / gmaps
            # (g, B+1) are this device's slot group; everything else is
            # replicated, so the carry updates below compute identically
            # on every device — only the slot distances are sharded, and
            # ``all_gather`` re-replicates their lists each round.
            seed, growth, cover_max = params[0, 0], params[0, 1], params[0, 2]
            Qp = q.shape[0]
            S = bounds.shape[1]

            def round_lists(r, unres):
                # one fused round at cut r: per-slot dense top-k of the
                # visited rows, the engine-exact radius cut, then the
                # global-order merge — op for op the host placed round
                # (``topk`` + ``_placed_cutmap`` + ``topk_merge_rows``)
                thr = r * r if form == "sq_l2" else r

                def one(blk, nv, sh, gm):
                    dist = _slot_form_dists(form, blk, q)
                    vm = (
                        unres
                        & (sh >= 0)
                        & (bounds[:, jnp.clip(sh, 0, S - 1)] <= r)
                    )
                    keep = (
                        jnp.arange(B, dtype=jnp.int32)[None, :] < nv
                    ) & vm[:, None]
                    dist = jnp.where(keep, dist, jnp.inf)
                    neg, idx = jax.lax.top_k(-dist, kk)
                    d = -neg
                    kp = d <= thr
                    dm = jnp.where(
                        kp,
                        jnp.sqrt(d) if form == "sq_l2" else d,
                        jnp.inf,
                    ).astype(jnp.float32)
                    gi = jnp.where(kp, gm[idx], sentinel).astype(jnp.int32)
                    if kk < k_eff:
                        dm = jnp.concatenate(
                            [dm, jnp.full((Qp, k_eff - kk), jnp.inf,
                                          jnp.float32)], 1
                        )
                        gi = jnp.concatenate(
                            [gi, jnp.full((Qp, k_eff - kk), sentinel,
                                          jnp.int32)], 1
                        )
                    return dm, gi

                dg, ig = jax.vmap(one)(blocks, nvalid, shards, gmaps)
                da = jax.lax.all_gather(dg, axis).reshape(
                    n_slots, Qp, k_eff
                )
                ia = jax.lax.all_gather(ig, axis).reshape(
                    n_slots, Qp, k_eff
                )
                d_all = jnp.transpose(da, (1, 0, 2)).reshape(
                    Qp, n_slots * k_eff
                )
                i_all = jnp.transpose(ia, (1, 0, 2)).reshape(
                    Qp, n_slots * k_eff
                )
                # ascending (dist, global idx) prefix == the sequential
                # ``topk_merge_rows`` fold (lexicographic top-k is
                # associative; each global index lives in exactly one slot)
                sd, si = jax.lax.sort((d_all, i_all), num_keys=2)
                return sd[:, :k_eff], si[:, :k_eff]

            def body(carry):
                pool_d, pool_i, unres, r, t, res_round, radii = carry
                pend = jnp.where(
                    unres & jnp.isfinite(floors), floors, jnp.inf
                )
                mn = jnp.min(pend)
                base = jnp.where(jnp.isfinite(mn), mn, jnp.float32(0.0))
                r1 = jnp.where(
                    t == 0,
                    jnp.maximum(jnp.maximum(seed, base), jnp.float32(1e-12)),
                    jnp.maximum(r * growth, base),
                )
                # the last allowed round forces the cut past every cover
                # bound: the pool is then provably complete and every row
                # resolves, so a float32 growth stall can't spin forever
                r1 = jnp.where(
                    t >= max_rounds - 1, jnp.maximum(r1, cover_max), r1
                )
                nd, ni = round_lists(r1, unres)
                # REPLACE unresolved rows (the round is complete within
                # its cut; merging smaller-cut pools would duplicate)
                pool_d = jnp.where(unres[:, None], nd, pool_d)
                pool_i = jnp.where(unres[:, None], ni, pool_i)
                if self_mode:
                    has_self = (pool_i == sid[:, None]).any(axis=1)
                    kth = jnp.where(
                        has_self, pool_d[:, k_eff - 1], pool_d[:, k_eff - 2]
                    )
                else:
                    kth = pool_d[:, k_eff - 1]
                resolved = unres & ((kth <= r1) | (r1 >= cover))
                res_round = jnp.where(resolved, t, res_round)
                radii = radii.at[t].set(r1)
                return (pool_d, pool_i, unres & ~resolved, r1,
                        t + 1, res_round, radii)

            init = (
                jnp.full((Qp, k_eff), jnp.inf, jnp.float32),
                jnp.full((Qp, k_eff), sentinel, jnp.int32),
                alive0,
                jnp.float32(0.0),
                jnp.int32(0),
                jnp.full((Qp,), -1, jnp.int32),
                jnp.zeros((max_rounds,), jnp.float32),
            )
            pool_d, pool_i, _, _, t, res_round, radii = jax.lax.while_loop(
                lambda c: (c[4] < max_rounds) & jnp.any(c[2]), body, init
            )
            # replicated results leave through a tiled leading slot axis
            # (check_vma=False: out_specs must mention the mesh axis);
            # the host wrapper takes [0]
            return (
                pool_d[None], pool_i[None], res_round[None], radii[None],
                jnp.reshape(t, (1,)),
            )

        fn = jax.shard_map(
            local,
            mesh=self.mesh,
            in_specs=(
                P(axis, None, None),  # blocks
                P(axis),              # valid-row counts
                P(axis),              # shard id per slot
                P(axis, None),        # global-index lookup per slot
                P(None, None),        # queries
                P(None),              # self ids
                P(None, None),        # (Qp, S) shard lower bounds
                P(None),              # per-query floor (nearest shard)
                P(None),              # per-query cover (cloud covered)
                P(None),              # initially-unresolved mask
                P(None, None),        # (seed, growth, cover_max)
            ),
            out_specs=(
                P(axis, None, None), P(axis, None, None),
                P(axis, None), P(axis, None), P(axis),
            ),
            check_vma=False,
        )
        return jax.jit(fn)

    def fused_rounds(self, space: str, form: str, queries, self_ids,
                     bounds, floors, cover, alive0, slot_gmaps, *,
                     seed: float, growth: float, k_eff: int,
                     self_mode: bool, sentinel: int, max_rounds: int = 64):
        """Run the WHOLE shared-cut round schedule as ONE device program.

        queries (Qp, dim) f32; self_ids (Qp,) global id or -1; bounds
        (Qp, n_shards) f32 deflated lower bounds; floors/cover (Qp,) f32;
        alive0 (Qp,) bool — padding rows False (they never search);
        slot_gmaps: per-slot (block_rows + 1,) local-row -> global-index
        lookups (row ``block_rows`` = ``sentinel``).

        Returns host arrays ``(pool_d (Qp, k_eff) mapped dists, pool_i
        (Qp, k_eff) global idxs, res_round (Qp,) resolution round or -1,
        radii (n_executed,) the schedule actually run, n_executed)``.
        """
        q = np.ascontiguousarray(queries, np.float32)
        sid = np.ascontiguousarray(self_ids, np.int32)
        b32 = np.ascontiguousarray(bounds, np.float32)
        fl32 = np.ascontiguousarray(floors, np.float32)
        cv32 = np.ascontiguousarray(cover, np.float32)
        al = np.ascontiguousarray(alive0, bool)
        cover_max = float(cv32[al].max()) if al.any() else 0.0
        shard_of = np.asarray([s for s, _, _ in self.slots], np.int32)
        gmaps = np.ascontiguousarray(np.stack(slot_gmaps), np.int32)
        params = np.asarray(
            [[seed, growth, cover_max]], np.float32
        )
        fn = self._fused_rounds_fn(
            form, int(k_eff), bool(self_mode), int(max_rounds),
            int(sentinel),
        )
        pd, pi, rr, radii, t = fn(
            self._placed_blocks(space), self._placed_nvalid(), shard_of,
            gmaps, q, sid, b32, fl32, cv32, al, params,
        )
        self.dispatches += 1
        n_exec = int(np.asarray(t)[0])
        return (
            np.array(pd[0]), np.array(pi[0]), np.array(rr[0]),
            np.array(radii[0][:n_exec]), n_exec,
        )

    # -- load spreading ----------------------------------------------------

    def slots_of(self, shard: int) -> list:
        return [j for j, (s, _, _) in enumerate(self.slots) if s == shard]

    def occupancy(self) -> list:
        """Points resident per device (contiguous slot groups under the
        1-D NamedSharding: device i owns slots [i*g, (i+1)*g))."""
        g = self.n_slots // self.n_devices
        return [
            int(sum(hi - lo for _, lo, hi in self.slots[i * g:(i + 1) * g]))
            for i in range(self.n_devices)
        ]

    def rebalance(self, shard: int) -> bool:
        """Split the named shard's largest slot across a free slot — two
        half-blocks of contiguous ascending rows, so slot answers union to
        exactly the shard answer.  Shapes are unchanged (same slot count,
        same block rows): no recompile, just a re-placement of the block
        arrays.  Returns False when no free slot or nothing to split."""
        free = [j for j, (s, _, _) in enumerate(self.slots) if s < 0]
        if not free:
            return False
        mine = [(hi - lo, j) for j, (s, lo, hi) in enumerate(self.slots)
                if s == shard and hi - lo >= 2]
        if not mine:
            return False
        _, j = max(mine)
        s, lo, hi = self.slots[j]
        mid = (lo + hi) // 2
        self.slots[j] = (s, lo, mid)
        self.slots[free[0]] = (s, mid, hi)
        self._invalidate_placement()
        self.rebalances += 1
        return True
