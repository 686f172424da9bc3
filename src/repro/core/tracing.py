"""The engine's tracing vocabulary: operator scopes and host spans.

Two kinds of name, each defined once here so that whatever reads a trace
finds them after a refactor:

* **Operator scopes** (``rel.*``) wrap the body of each relational operator
  in ``jax.named_scope``.  They change only the ``op_name`` metadata of the
  HLO the operator emits, never the operations, so every device operation
  of a profiled program can be charged to the operator that emitted it,
  whatever implements it (a direct-address gather, a ``searchsorted``
  binary search or a Pallas probe kernel).  Where scopes nest (a
  ``compact`` inside a group-by) the innermost ``rel.*`` component of an
  ``op_name`` is the owner.
* **Host spans** (``serve.*``) mark the phases of ``QueryServer.submit``.
  Each opens a ``jax.profiler.TraceAnnotation``, which a running profiler
  records on its host plane on the device trace's clock, and adds its host
  seconds to a :class:`Phases` counter, which is read without a profiler.
  There is no switch: with the profiler off a span costs a few
  microseconds (two clock reads, an idle annotation, a dict update).
"""
from __future__ import annotations

import contextlib
import functools
import time

import jax

# operator scopes (core/relational.py, core/exchange.py)
JOIN_BUILD = "rel.join_build"    # build_index: direct scatter, argsort, buckets
JOIN_PROBE = "rel.join_probe"    # probe_index: direct gather, searchsorted, hash
JOIN_TAKE = "rel.join_take"      # gathers of build columns through the index
GROUP_BY = "rel.group_by"        # group_aggregate, every path
COMPACT = "rel.compact"          # front compaction
ORDER = "rel.order"              # sort_by, sort_limit
EXCHANGE = "rel.exchange"        # shuffles, broadcasts, gathers, all-reduces
SCOPES = (JOIN_BUILD, JOIN_PROBE, JOIN_TAKE, GROUP_BY, COMPACT, ORDER,
          EXCHANGE)

# host spans (serve/server.py), all but PLACE inside SUBMIT
PLACE = "serve.place"            # partition the tables, put them on the mesh
SUBMIT = "serve.submit"          # one request; args request=<n>, template=
BIND = "serve.bind"              # bind the parameters, put them on device
LOOKUP = "serve.lookup"          # find (or build) the executable
DISPATCH = "serve.dispatch"      # enqueue the program (compile on a miss)
WAIT = "serve.wait"              # block on the overflow flag: the device
RERUN = "serve.rerun"            # the conservative rerun after an overflow
FETCH = "serve.fetch"            # copy the result to the host
SPANS = (PLACE, SUBMIT, BIND, LOOKUP, DISPATCH, WAIT, RERUN, FETCH)


def op_scope(name: str):
    """Decorator: trace the function's body inside ``jax.named_scope(name)``.

    A scope is entered anew on every call: one ``named_scope`` object used
    as a decorator keeps its state on itself and so cannot be entered by two
    threads at once (programs are compiled on thread pools)."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap


class Phases:
    """Host seconds of each span name: count, total and longest."""

    def __init__(self):
        self._stats: dict[str, list] = {}

    @contextlib.contextmanager
    def span(self, name: str, **args):
        """Time the block as ``name``; ``args`` go to the profiler's event."""
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name, **args):
                yield
        finally:
            dt = time.perf_counter() - t0
            s = self._stats.get(name)
            if s is None:
                self._stats[name] = [1, dt, dt]
            else:
                s[0] += 1
                s[1] += dt
                s[2] = max(s[2], dt)

    def stats(self) -> dict[str, dict[str, float]]:
        """``{name: {"count", "total_s", "max_s"}}`` since construction."""
        return {name: {"count": n, "total_s": tot, "max_s": top}
                for name, (n, tot, top) in self._stats.items()}
