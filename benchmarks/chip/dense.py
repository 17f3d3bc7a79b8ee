"""The dense kNN engine's program and the operations it must do, for the
readers of the dense cell's per-layer metrics.

The count comes from shapes alone: the cross term ``q @ p.T`` of the
engine's ``|q|^2 + |p|^2 - 2 q.p`` is 2·d flops per query-point pair.  The
norms, the sum and the top-k are not counted, so a share computed from it
never overstates the matrix unit's work.
"""

from __future__ import annotations

# jit name of the dense engine's program (``repro.core.brute._brute_impl``,
# jitted under its own name); not a stable name
DENSE_PROGRAM = "jit__brute_impl"


def cross_term_flops(rows: int, n: int, d: int) -> int:
    """Flops of the cross term for ``rows`` query rows against ``n``
    points of ``d`` dimensions."""
    return 2 * int(d) * int(n) * int(rows)
