"""Fused on-device radius-growth loop — one XLA dispatch per TrueKNN search.

The host driver (``repro.api.backends.trueknn._run_knn``) runs the paper's
expand-until-k iteration on the host: every round is a separate device
dispatch followed by a host sync for the convergence check.  That is the
repeated-launch tax RTNN identifies as the dominant cost of re-running
traversal setup.  This module moves the whole round loop into a single
jitted program:

* ``build_schedule`` transcribes the host driver's *control flow* — the
  radius sequence is data-independent (geometric growth, stop/cap
  handling, the brute-equivalent guard, the 4x-extent clamp), so the
  rounds the device loop may need are known up front, and each round's
  lattice-snapped grid comes from the index's existing grid cache.
* ``fused_search`` runs one ``jax.lax.while_loop`` whose carry holds the
  per-query best-k heap, an on-device unresolved mask, per-round test
  counters and the resolution round per query.  The predicate reduces the
  unresolved mask *on device*; each round body runs the same
  ``_chunk_candidates`` the per-round host driver traces, selected by
  ``lax.switch`` over the deduped per-grid branches, with the squared
  radius as traced data.  A round visits only the chunks that hold
  unresolved rows (they are ordered first), in chunks sized to the grid's
  cap (``round_chunk``), so late rounds cost what their survivors cost.
  An optional brute tail (``_brute_impl``, the exact oracle) runs over
  the rows still unresolved, the same way.

Because the loop body and the tail call the *same* jitted subroutines as
the host driver on the same operands, answers are bit-identical to the
host loop by construction — not by tolerance.  The only host<->device
traffic per search is the final result fetch: one dispatch however many
rounds run.

Caveat: queries with non-finite coordinates are treated as padding by the
fused driver (they can never resolve, and a mask that can never clear
would keep the while-loop spinning); the host driver (``fused=False``)
remains the oracle for such pathological rows.
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from repro import trace

from .brute import _brute_impl
from .fixed_radius import _chunk_candidates, round_chunk, round_slots
from .grid import _next_pow2, stencil_offsets

__all__ = ["FusedSchedule", "FusedResult", "build_schedule", "fused_search"]


def _floor_pow2(x: int) -> int:
    return 1 << max(0, int(x).bit_length() - 1)


@dataclasses.dataclass(frozen=True)
class FusedSchedule:
    """The data-independent round plan of one fused search.

    ``radii[t]`` is round t's search radius and ``grids[t]`` its
    lattice-snapped grid (grids repeat once the lattice cap is reached —
    the device program dedupes them into ``lax.switch`` branches).
    ``tail_mode`` says what finishes still-unresolved queries after the
    last round: ``"plain"`` (exact brute tail, unbounded — stop_radius is
    None or the brute-equivalent guard fired), ``"capped"`` (brute tail
    re-cut at the hybrid cap), or ``"none"`` (stop_radius tails keep
    their partial lists).
    """

    radii: tuple
    grids: tuple
    cache_hits: tuple
    tail_mode: str
    stop_radius: object  # Optional[float]

    def signature(self) -> tuple:
        """Shape-defining key of the compiled fused program (executable-
        cache bucketing): round count, per-round grid shapes, tail form."""
        return (
            len(self.radii),
            tuple((g.table_size, g.cap) for g in self.grids),
            self.tail_mode,
        )


@dataclasses.dataclass
class FusedResult:
    """Raw device outputs of one fused search (host numpy, post-fetch).

    ``dists`` are true L2 (sqrt applied on device); ``unresolved`` is the
    pre-tail mask (rows the while-loop could not resolve); ``tests[t]``
    counts candidate distance evaluations charged to round t and
    ``slots[t]`` the candidate slots it gathered (its live rows padded to
    its chunk, times 3^d·cap; computed on the host);
    ``n_executed`` is how many scheduled rounds actually ran before the
    on-device predicate cleared.
    """

    dists: np.ndarray  # (Q, k) float32
    idxs: np.ndarray  # (Q, k) int32
    found: np.ndarray  # (Q,) int32
    unresolved: np.ndarray  # (Q,) bool, pre-tail
    resolved_round: np.ndarray  # (Q,) int32, -1 = never in-loop
    tests: np.ndarray  # (n_sched,) float64
    slots: np.ndarray  # (n_sched,) int64, 0 for rounds that did not run
    n_executed: int
    q_pad: int


def build_schedule(index, r0: float, *, stop_radius=None,
                   cap_exact: bool = False) -> FusedSchedule:
    """Transcribe the host driver's round schedule for a start radius.

    This is ``_run_knn``'s loop control with the data-dependent early
    exits removed: the device loop applies those itself (it stops growing
    the moment the unresolved mask clears), so scheduling *more* rounds
    than a batch ends up needing costs nothing at run time.  Grids come
    from ``index._grid_for`` — same call order as the host driver, so the
    lattice cache sees the identical build/hit sequence for the rounds
    that execute.
    """
    radii, grids, hits = [], [], []
    r = float(r0)
    ridx = 0
    force_brute_tail = False
    clamp_r = 4.0 * index._extent
    while ridx < index._max_rounds:
        at_cap = False
        if stop_radius is not None:
            if cap_exact:
                # hybrid cap: boundary round searches exactly the cap
                # radius (jump straight there on the last budgeted round)
                if r >= stop_radius or ridx == index._max_rounds - 1:
                    r = float(stop_radius)
                    at_cap = True
            elif r > stop_radius:
                break
        grid, hit = index._grid_for(r)
        if index._grid_no_better_than_brute(grid, stop_radius, cap_exact):
            force_brute_tail = True
            break
        radii.append(r)
        grids.append(grid)
        hits.append(hit)
        ridx += 1
        if at_cap:
            break
        # single-cell grid covering the cloud diagonal: the round was a
        # brute-force pass; if queries still don't resolve, growing cannot
        # help — the exact tail finishes them
        if all(res == 1 for res in grid.res) and r * r >= index._sq_diag:
            force_brute_tail = True
            break
        r *= index._growth
        if r > clamp_r:
            r = clamp_r
    tail_mode = (
        ("capped" if cap_exact else "plain")
        if (force_brute_tail or stop_radius is None)
        else "none"
    )
    return FusedSchedule(
        radii=tuple(radii),
        grids=tuple(grids),
        cache_hits=tuple(hits),
        tail_mode=tail_mode,
        stop_radius=stop_radius,
    )


def _over_rows(rows_mask, chunk: int, fn, init):
    """Visit only the rows set in ``rows_mask``, ``chunk`` rows at a time:
    the set rows are ordered first (stably, in row order) and a dynamic
    trip count covers just the chunks that hold them.  ``fn(rows, live,
    state)`` gets each chunk's row ids and which of them are set; rows of
    the last chunk past the set ones must be left untouched."""
    order = jnp.argsort(~rows_mask, stable=True).astype(jnp.int32)
    n_chunks = (jnp.sum(rows_mask, dtype=jnp.int32) + chunk - 1) // chunk

    def body(i, state):
        rows = jax.lax.dynamic_slice(order, (i * chunk,), (chunk,))
        return fn(rows, rows_mask[rows], state)

    return jax.lax.fori_loop(0, n_chunks, body, init)


@lru_cache(maxsize=None)
def _fused_fn(branch_tables: tuple, branch_of: tuple, has_tail: bool,
              k: int, chunk: int, tail_chunk: int):
    """The jitted multi-round driver for one schedule *shape*.

    Static key: per-branch hash-table sizes, the round->branch map, the
    tail form and the chunk geometry.  Everything else — the grids' bucket
    arrays, the per-round squared radii, the query batch — is traced, so
    warm batches whose schedules share a shape reuse the executable.

    The jitted function is called ``run`` (its program is ``jit_run``), and
    its ops carry the named scopes ``trueknn.fused`` (all of it),
    ``trueknn.round.b<b>`` (grid branch ``b``) and ``trueknn.tail``: a
    device trace reads per-round and tail time from them.
    """
    n_sched = len(branch_of)
    branch_lookup = jnp.asarray(np.asarray(branch_of, np.int32))

    @jax.named_scope("trueknn.fused")
    def run(pts, grids, q, qid, r2s):
        n, d = pts.shape
        q_pad = q.shape[0]
        offs = jnp.asarray(stencil_offsets(d))

        def make_branch(b):
            buckets, planes, origin, inv_cell, res_arr = grids[b]
            table_size = branch_tables[b]
            cb = round_chunk(chunk, d, buckets.shape[1])

            @jax.named_scope(f"trueknn.round.b{b}")
            def branch(carry):
                best_d2, best_i, found, unres, res_round, tests_vec, t = carry
                r2 = r2s[t]

                def one_chunk(rows, live, state):
                    bd, bi, fd, tests = state
                    top_d2, top_i, fnd, valid = _chunk_candidates(
                        buckets, planes, origin, inv_cell, res_arr, offs,
                        q[rows], qid[rows], r2,
                        n=n, table_size=table_size, k=k,
                    )
                    # only still-unresolved rows are charged (resolved and
                    # padding rows never reach the host driver's kernel)
                    tests = tests + jnp.sum(
                        valid & live[:, None], dtype=jnp.float32
                    )
                    # REPLACE (not merge) for unresolved rows: every round
                    # re-searches from scratch at the larger radius, exactly
                    # like the host driver's per-round overwrite
                    bd = bd.at[rows].set(
                        jnp.where(live[:, None], top_d2, bd[rows])
                    )
                    bi = bi.at[rows].set(
                        jnp.where(live[:, None], top_i, bi[rows])
                    )
                    fd = fd.at[rows].set(jnp.where(live, fnd, fd[rows]))
                    return bd, bi, fd, tests

                best_d2, best_i, found, tests = _over_rows(
                    unres, cb, one_chunk,
                    (best_d2, best_i, found, jnp.float32(0)),
                )
                res_now = unres & (found >= k)
                res_round = jnp.where(res_now, t, res_round)
                tests_vec = tests_vec.at[t].set(tests)
                return (best_d2, best_i, found, unres & ~res_now,
                        res_round, tests_vec, t + jnp.int32(1))

            return branch

        branches = [make_branch(b) for b in range(len(branch_tables))]

        def cond(carry):
            return (carry[6] < n_sched) & jnp.any(carry[3])

        def body(carry):
            return jax.lax.switch(branch_lookup[carry[6]], branches, carry)

        init = (
            jnp.full((q_pad, k), jnp.inf, jnp.float32),
            jnp.full((q_pad, k), n, jnp.int32),
            jnp.zeros((q_pad,), jnp.int32),
            jnp.isfinite(q[:, 0]),  # padding rows start resolved
            jnp.full((q_pad,), -1, jnp.int32),
            jnp.zeros((n_sched,), jnp.float32),
            jnp.int32(0),
        )
        best_d2, best_i, found, unres, res_round, tests_vec, t = (
            jax.lax.while_loop(cond, body, init)
        )
        best_d = jnp.sqrt(best_d2)
        if has_tail:
            # exact oracle for whatever the loop left unresolved, inlined
            # into the same program (jit-of-jit) over just those rows:
            # identical ops to the host driver's brute_knn_engine tail.
            # Rows are replaced wholesale, as the host does; the hybrid
            # re-cut and the found recount are host-side post-filters in
            # both drivers.
            def tail_chunk_fn(rows, live, state):
                bd_, bi_ = state
                d2t, it = _brute_impl(
                    pts, q[rows], qid[rows], k=k,
                    chunk=tail_chunk, exclude_self=True, metric="l2",
                )
                bd_ = bd_.at[rows].set(
                    jnp.where(live[:, None], jnp.sqrt(d2t), bd_[rows])
                )
                bi_ = bi_.at[rows].set(jnp.where(live[:, None], it, bi_[rows]))
                return bd_, bi_

            with jax.named_scope("trueknn.tail"):
                best_d, best_i = _over_rows(
                    unres, tail_chunk, tail_chunk_fn, (best_d, best_i)
                )
        return best_d, best_i, found, unres, res_round, tests_vec, t

    return jax.jit(run)


def fused_search(points, schedule: FusedSchedule, queries, query_ids,
                 k: int, *, chunk: int = 2048) -> FusedResult:
    """Run one whole multi-round search as a single jitted dispatch.

    ``points`` is the resident cloud (host or device array), ``queries``
    (Q, d) with ``query_ids`` (Q,) int32 (the dataset id for self-queries,
    N otherwise).  The batch is padded once to a power of two; the only
    host sync is the final result fetch.
    """
    with trace.span("trueknn.dispatch"):
        q = jnp.asarray(queries, jnp.float32)
        qid = jnp.asarray(query_ids, jnp.int32)
        q_total = q.shape[0]
        q_pad = _next_pow2(max(q_total, 1))
        chunk = _floor_pow2(min(int(chunk), q_pad))
        if q_pad > q_total:
            q = jnp.concatenate(
                [q, jnp.full((q_pad - q_total, q.shape[1]), jnp.inf,
                             q.dtype)]
            )
            qid = jnp.concatenate(
                [qid, jnp.full((q_pad - q_total,),
                               schedule.grids[0].n_points, qid.dtype)]
            )
        pts = jnp.asarray(points, jnp.float32)

        # dedupe repeated grids (post-lattice-cap rounds share the
        # single-cell grid) into switch branches; the round->branch map is
        # static
        seen: dict = {}
        branch_of = []
        branch_grids = []
        for g in schedule.grids:
            b = seen.get(id(g))
            if b is None:
                b = len(branch_grids)
                seen[id(g)] = b
                branch_grids.append(g)
            branch_of.append(b)
        grid_args = tuple(
            (g.buckets, g.planes, g.origin, g.inv_cell, g.res_arr)
            for g in branch_grids
        )
        # host numpy f32 square == device f32 square (same IEEE multiply)
        r2s = jnp.asarray(np.asarray(schedule.radii, np.float32) ** 2)

        fn = _fused_fn(
            tuple(g.table_size for g in branch_grids),
            tuple(branch_of),
            schedule.tail_mode != "none",
            int(k),
            chunk,
            min(512, q_pad),
        )
        bd, bi, found, unres, res_round, tests, t = fn(
            pts, grid_args, q, qid, r2s)
    with trace.span("trueknn.fetch"):
        res_round = np.array(res_round[:q_total])
        unres = np.array(unres[:q_total])
        n_executed = int(t)
        # round t searched the rows it had not resolved yet: those that
        # resolved at t or later, and those the loop never resolved
        slots = np.zeros((len(schedule.radii),), np.int64)
        for r in range(n_executed):
            live = int(np.sum(res_round >= r)) + int(np.sum(unres))
            slots[r] = round_slots(live, chunk, q.shape[1],
                                   schedule.grids[r].cap)
        return FusedResult(
            dists=np.array(bd[:q_total]),
            idxs=np.array(bi[:q_total]),
            found=np.array(found[:q_total]),
            unresolved=unres,
            resolved_round=res_round,
            tests=np.asarray(tests, np.float64),
            slots=slots,
            n_executed=n_executed,
            q_pad=q_pad,
        )
