"""The ``NeighborIndex`` protocol and ``build_index`` entry point.

The paper's workload shape is *build once, query many*: the point cloud is
resident, query batches stream in, and the search structure amortizes across
batches.  A ``NeighborIndex`` is that resident handle; ``query`` is the only
hot-path call.  Backends are looked up in the string-keyed registry so new
engines plug in without touching call sites.

Since QuerySpec v2, ``query`` takes a typed spec (``KnnSpec`` /
``RangeSpec`` / ``HybridSpec``) plus a metric name, and a thin planner
(``repro.api.planner``) routes it: native per-backend ``execute_*`` hooks
when the backend has a fast path, generic plans (knn-then-filter for
hybrid, counted/oversized-k sweeps for range, monotone L2 reduction or the
exact brute engine for non-native metrics) otherwise.  The PR-1 signature
``query(queries, k, radius=..., stop_radius=...)`` survives as a deprecated
adapter that constructs a ``KnnSpec``.

Since the QueryPlan redesign, the explicit two-phase form is
``plan = index.prepare(spec, metric=...)`` then ``plan(queries)`` — plan
construction and compiled-executable reuse are amortized across batches
(see ``repro.api.plan``), and ``query`` is a thin prepare-then-call
wrapper kept for one-shot use.
"""

from __future__ import annotations

import abc
import inspect
from typing import Optional, Union

import numpy as np

from repro import trace
from repro.core.result import KNNResult, RangeResult

from .metrics import Metric, get_metric
from .query import HybridSpec, KnnSpec, QuerySpec, RangeSpec, warn_deprecated_once
from .registry import get_backend

__all__ = ["NeighborIndex", "build_index"]


class NeighborIndex(abc.ABC):
    """A built search structure over a resident point cloud.

    Subclasses ingest ``points`` once in ``__init__`` (the *build*) and
    answer ``query`` repeatedly, carrying whatever state lets later batches
    go faster (cached grids, warm-start radii, device-resident shards).

    Backends implement ``execute_knn`` (mandatory) and may implement
    ``execute_range`` / ``execute_hybrid`` native fast paths; the planner
    falls back to generic plans where a hook raises ``NotImplementedError``.
    ``native_metrics`` names the metrics the backend's own engine handles;
    for anything else the planner either searches a transformed companion
    cloud (metrics with an exact monotone L2 reduction, e.g. cosine) or
    answers through the exact metric-aware brute engine.
    """

    backend_name: str = "?"
    #: metrics the backend's engine computes natively (planner contract)
    native_metrics: frozenset = frozenset({"l2"})
    #: cfg knobs that are radii in query-metric units; mapped through
    #: ``metric.radius_to_l2`` when a metric companion view is built
    radius_cfg_keys: tuple = ()
    #: what KnnSpec.start_radius means to this backend: a "seed" for the
    #: radius schedule (safe for generic plans to ignore) or a hard
    #: "bound" on returned neighbors (generic plans must post-filter)
    knn_start_radius_semantics: str = "seed"

    def __init__(self, points):
        pts = np.asarray(points, dtype=np.float32)
        assert pts.ndim == 2, f"points must be (N, d), got {pts.shape}"
        self._pts = pts
        self._metric_views: dict = {}  # metric name -> companion index
        self._generation = 0

    # -- introspection ----------------------------------------------------

    @property
    def points(self) -> np.ndarray:
        """The resident cloud (host copy, (N, d) float32)."""
        return self._pts

    @property
    def n_points(self) -> int:
        return self._pts.shape[0]

    @property
    def dim(self) -> int:
        return self._pts.shape[1]

    @property
    def generation(self) -> int:
        """Monotone mutation counter: 0 for the life of an immutable
        backend; the mutable composite bumps it on every insert / delete /
        compaction.  ``QueryPlan`` captures it at prepare time and
        transparently re-prepares when it has moved (see
        ``repro.api.plan``), so no plan ever answers from pre-mutation
        routing state."""
        return self._generation

    @property
    def sentinel(self) -> int:
        """The padding id in ``KNNResult.idxs`` (one past the largest
        valid dataset id).  Equals ``n_points`` everywhere except the
        mutable composite, whose results carry *stable* ids that survive
        deletion."""
        return self.n_points

    def __len__(self) -> int:
        return self.n_points

    def _span_args(self) -> dict:
        """What the ``index.query`` span records of this index's state
        (``repro.trace``); backends extend this."""
        return {}

    def stats(self) -> dict:
        """Cumulative counters since build; backends extend this."""
        return {
            "backend": self.backend_name,
            "n_points": self.n_points,
            "dim": self.dim,
            "generation": self.generation,
            "metric_views": sorted(self._metric_views),
        }

    # -- mutation (mutable composite only) --------------------------------

    def insert(self, points) -> np.ndarray:
        """Add points to the resident cloud.  Immutable backends raise;
        build with ``backend="mutable"`` (or wrap an existing index via
        ``repro.api.mutable.make_mutable``) for streaming writes."""
        raise NotImplementedError(
            f"backend {self.backend_name!r} is immutable; build with "
            "backend='mutable' or wrap it: "
            "repro.api.mutable.make_mutable(index)"
        )

    def delete(self, ids) -> int:
        """Remove points by dataset id.  Immutable backends raise; see
        :meth:`insert`."""
        raise NotImplementedError(
            f"backend {self.backend_name!r} is immutable; build with "
            "backend='mutable' or wrap it: "
            "repro.api.mutable.make_mutable(index)"
        )

    # -- the hot path -----------------------------------------------------

    def query(
        self,
        queries,
        spec: Union[QuerySpec, int, None] = None,
        *,
        metric: str = "l2",
        k: Optional[int] = None,
        radius: Optional[float] = None,
        stop_radius: Optional[float] = None,
    ):
        """Answer ``spec`` over ``queries`` ((Q, d), or None to let the
        dataset query itself with self-exclusion).

        The spec says *what* to search (``KnnSpec(k)``, ``RangeSpec(r)``,
        ``HybridSpec(k, r)`` — see ``repro.api.query``), ``metric`` says in
        which distance (``repro.api.metrics``).  Returns ``KNNResult`` for
        knn/hybrid specs, ``RangeResult`` (ragged CSR) for range specs.

        Deprecated form: ``query(queries, k, radius=..., stop_radius=...)``
        (an int where the spec goes, or the ``k=`` keyword) adapts to
        ``KnnSpec(k, start_radius=radius, stop_radius=stop_radius)`` and
        warns once per process.
        """
        if isinstance(spec, (int, np.integer)):
            if k is not None:
                raise TypeError("query() got k twice (positional and keyword)")
            k, spec = int(spec), None
        if spec is None:
            if k is None:
                raise TypeError(
                    "query() needs a QuerySpec (e.g. KnnSpec(k=8)) — or the "
                    "deprecated k=... form"
                )
            warn_deprecated_once(
                "NeighborIndex.query:k",
                "NeighborIndex.query(queries, k, radius=..., stop_radius=...)"
                " is deprecated; pass a spec: query(queries, KnnSpec(k, "
                "start_radius=..., stop_radius=...))",
            )
            spec = KnnSpec(
                int(k), start_radius=radius, stop_radius=stop_radius
            )
        else:
            if not isinstance(spec, QuerySpec):
                raise TypeError(
                    f"spec must be a QuerySpec (KnnSpec / RangeSpec / "
                    f"HybridSpec), got {type(spec).__name__}"
                )
            if k is not None or radius is not None or stop_radius is not None:
                raise TypeError(
                    "pass either a QuerySpec or the legacy k/radius/"
                    "stop_radius keywords, not both"
                )
        from .plan import QueryPlan  # late import: plan imports index

        # thin prepare-then-call wrapper: a throwaway plan with legacy
        # shapes (no canonicalization), so one-shot callers see exactly the
        # engine shapes and counters they always did.  Hold a prepared plan
        # (``index.prepare``) to amortize planning and compiled executables.
        args = {}
        if trace.enabled():
            rows = self.n_points if queries is None else len(queries)
            args = dict(self._span_args(), rows=rows)
        with trace.span("index.query", **args):
            return QueryPlan(self, spec, metric, canonical_shapes=False)(
                queries)

    def prepare(
        self,
        spec: QuerySpec,
        *,
        metric: str = "l2",
        canonical_shapes: bool = True,
    ):
        """Prepare a reusable :class:`repro.api.plan.QueryPlan` for
        ``spec``/``metric``: ``plan = index.prepare(KnnSpec(8))`` then
        ``plan(queries)`` per batch.  Answers are identical to ``query``;
        repeated batches reuse the constructed route and the shape-bucketed
        compiled executables (``canonical_shapes=False`` disables the
        pow2 shape canonicalization and keeps exact legacy engine shapes).
        ``plan.explain()`` returns the structured route tree."""
        from .plan import QueryPlan

        return QueryPlan(
            self, spec, metric, canonical_shapes=canonical_shapes
        )

    # -- backend capability hooks (planner contract) ----------------------

    def supports_knn_spec(self, spec: KnnSpec) -> bool:
        """Whether ``execute_knn`` serves this spec variant natively; the
        planner routes unsupported variants to the cached companion-trueknn
        fallback at *plan-construction* time (backends with no radius
        schedule reject ``stop_radius`` here)."""
        return True

    def plan_details(self, spec: QuerySpec, metric: Metric) -> tuple:
        """(tag, props, children) of this backend's native plan node —
        what ``plan.explain()`` shows for the native route.  ``tag`` is
        the legacy ``timings["plan"]`` string the route emits (static
        prefix for dynamic tags); composite backends add per-shard child
        plan nodes."""
        return "native", {}, []

    @abc.abstractmethod
    def execute_knn(
        self, queries, spec: KnnSpec, metric: Metric, ctx=None
    ) -> KNNResult:
        """Native kNN path.  ``metric`` is guaranteed ∈ ``native_metrics``;
        ``ctx`` is the executing plan's ``PlanContext`` (None for bare
        calls)."""

    def execute_range(
        self, queries, spec: RangeSpec, metric: Metric, ctx=None
    ) -> RangeResult:
        """Native range path; raise NotImplementedError for the generic
        oversized-k sweep."""
        raise NotImplementedError

    def execute_hybrid(
        self, queries, spec: HybridSpec, metric: Metric, ctx=None
    ) -> KNNResult:
        """Native radius-capped kNN; raise NotImplementedError for the
        generic knn-then-filter plan."""
        raise NotImplementedError

    def knn_spec_radius_cut(self, spec: KnnSpec):
        """The radius bound this backend applies to a ``KnnSpec`` answer
        (None = unbounded).  Generic plans honor it, so a spec keeps one
        meaning on a backend whatever metric route answers it: "bound"
        backends cap at ``start_radius``, "seed" backends treat it as a
        scheduling hint with no effect on the answer set."""
        if self.knn_start_radius_semantics == "bound":
            return spec.start_radius
        return None

    # -- metric companion views -------------------------------------------

    def metric_view(self, metric: Metric) -> "NeighborIndex":
        """Companion index of the same backend over the metric's transformed
        cloud (built lazily, cached for the life of this index).  This is
        the Arkade monotone-transform trick: grids, round schedules and
        warm-start state all operate in transformed space, and only
        distances/radii are mapped at the planner boundary."""
        assert metric.has_l2_view, metric.name
        view = self._metric_views.get(metric.name)
        if view is None:
            cfg = dict(getattr(self, "_build_cfg", None) or {})
            # radius-valued knobs were given in query-metric units; the
            # companion searches transformed (L2) space, so map them
            for key in self.radius_cfg_keys:
                if cfg.get(key) is not None:
                    cfg[key] = metric.radius_to_l2(float(cfg[key]))
            view = type(self)(metric.transform_points(self._pts), **cfg)
            view._build_cfg = cfg
            self._metric_views[metric.name] = view
        return view


def _valid_cfg_keys(cls) -> Optional[set]:
    """Keyword knobs of ``cls.__init__`` past (self, points); None means
    "accepts anything" (a **cfg backend validates its own)."""
    params = list(inspect.signature(cls.__init__).parameters.values())[2:]
    if any(p.kind is inspect.Parameter.VAR_KEYWORD for p in params):
        return None
    return {
        p.name
        for p in params
        if p.kind
        in (
            inspect.Parameter.POSITIONAL_OR_KEYWORD,
            inspect.Parameter.KEYWORD_ONLY,
        )
    }


def build_index(points, *, backend: str = "trueknn", **cfg) -> NeighborIndex:
    """Build a resident neighbor-search index.

    Usage::

        from repro.api import KnnSpec, RangeSpec
        index = build_index(pts, backend="trueknn")
        res = index.query(batch, KnnSpec(k=8))        # KNNResult
        rng = index.query(batch, RangeSpec(radius=r)) # RangeResult (CSR)
        ...                                           # later batches reuse grids

    ``cfg`` is passed to the backend constructor (each documents its own
    knobs); unknown keys are rejected up front with the backend's valid
    knob list, so a typo like ``growht=2.0`` fails loudly instead of as a
    bare TypeError.  Registered backends: see ``available_backends()``.
    """
    cls = get_backend(backend)
    valid = _valid_cfg_keys(cls)
    if valid is not None:
        unknown = sorted(set(cfg) - valid)
        if unknown:
            raise ValueError(
                f"unknown config key(s) {unknown} for backend {backend!r}; "
                f"valid knobs: {sorted(valid)}"
            )
    index = cls(points, **cfg)
    assert isinstance(index, NeighborIndex), (
        f"backend {backend!r} ({cls.__name__}) must subclass NeighborIndex"
    )
    # remembered so metric companion views rebuild with the same knobs
    index._build_cfg = dict(cfg)
    return index
