"""Host spans in the profiler's trace, off until switched on.

``span(name, **args)`` marks a stretch of host work.  While spans are
enabled it is ``jax.profiler.TraceAnnotation(name, **args)``, so the span
lands in the profiler's own trace, on the same clock as the device's ops,
with ``args`` as its stats.  While they are off it is one shared no-op
context, and its whole cost is a check of a module-level flag.  Turn spans
on around a ``jax.profiler`` capture::

    jax.profiler.start_trace(logdir)
    repro.trace.enable(True)
    ...                       # searches, served batches
    repro.trace.enable(False)
    jax.profiler.stop_trace()

Span names are ``<layer>.<step>``; a child span nests inside its parent on
the same thread:

  index.query          ``NeighborIndex.query``: one whole search
                       (args ``rows``; TrueKNN adds ``search``, the
                       index's batch number, and ``start_step``, the
                       lattice step its warm start begins at)
  trueknn.schedule     the round schedule and its grid-cache lookups
  trueknn.grid_build   one grid built on a cache miss (``step``, ``radius``)
  trueknn.dispatch     upload, padding and enqueue of the fused program
  trueknn.fetch        waiting for the device and copying results back
  trueknn.finish       rounds, warm-start EMA and ``KNNResult`` on the host
  brute.dispatch       the brute backend's kNN: padding, upload and enqueue
                       of the dense engine (``core.brute._brute_impl``)
  brute.fetch          waiting for the device and copying its results back
  server.batch         one coalesced batch of ``NeighborServer``
  server.execute       the part of that batch that holds the index

The fused program carries device-side names as ``jax.named_scope``
metadata, which costs nothing at run time: ``trueknn.fused`` around the
whole program, ``trueknn.round.b<b>`` around the ops of grid branch ``b``
and ``trueknn.tail`` around the exact brute tail.  The dense engine
(``_brute_impl``) names its distance block ``brute.dist`` and its top-k
``brute.select``; inside the fused program they nest under
``trueknn.tail``.

Counters stay with the layer that owns them (``index.stats()``,
``server.stats()``); this module records time only.
"""

from __future__ import annotations

import contextlib

import jax

__all__ = ["PREFIXES", "enable", "enabled", "span"]

#: name prefixes of the program's spans, for readers of a trace
PREFIXES = ("index.", "trueknn.", "brute.", "server.")

_on = False
_OFF = contextlib.nullcontext()


def enable(on: bool = True) -> None:
    """Switch the program's host spans on or off (process-wide)."""
    global _on
    _on = bool(on)


def enabled() -> bool:
    """Whether ``span`` currently writes to the profiler's trace."""
    return _on


def span(name: str, **args):
    """A host span named ``name`` while spans are enabled, else a no-op."""
    if not _on:
        return _OFF
    return jax.profiler.TraceAnnotation(name, **args)
