"""The engine's host spans in a profiler trace, beside the operator scopes
that :mod:`harness.trace` gives each device operation.

``QueryServer.submit`` wraps each of its phases in a ``TraceAnnotation``
named ``serve.<phase>`` (``repro.core.tracing``).  This module reads those
spans, with nothing but JAX, from the host planes, on the clock of the
benchmark's ``bench.submit q<N>`` spans (one thread writes both), and names
idle gaps by them.
"""
from __future__ import annotations

import dataclasses

from . import trace as tr

HOST_SPAN = "serve."
# the spans ``per_request_ms`` is given (``repro.core.tracing`` defines
# them; a test checks that it still does)
LAUNCH = ("serve.bind", "serve.lookup", "serve.dispatch")
FETCH = ("serve.fetch",)


@dataclasses.dataclass(frozen=True)
class HostSpan:
    name: str
    start_ns: float
    end_ns: float
    args: dict

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


def host_spans(profile) -> list[HostSpan]:
    """The ``serve.*`` annotations of the host planes, in start order."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [HostSpan(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
                    for ev in line.events if ev.name.startswith(HOST_SPAN)]
    return sorted(out, key=lambda s: (s.start_ns, -s.end_ns))


@dataclasses.dataclass
class Scoped:
    """A reduced trace and the engine's host spans."""
    summary: tr.Summary
    host: list[HostSpan]

    @classmethod
    def of(cls, summary: tr.Summary, profile) -> "Scoped":
        return cls(summary, host_spans(profile))

    def per_request_ms(self, names) -> float | None:
        """Per request (``bench.submit`` span), the summed length of the host
        spans named ``names`` inside it, averaged over the requests."""
        reqs = self.summary.spans
        if not reqs or not any(h.name in names for h in self.host):
            return None
        tot = sum(h.dur_ns for h in self.host if h.name in names
                  and any(r.start_ns <= h.start_ns <= r.end_ns for r in reqs))
        return tot / len(reqs) / 1e6

    def _label(self, t: float) -> str:
        reqs = [r for r in self.summary.spans if r.start_ns <= t <= r.end_ns]
        if not reqs:
            return "between requests"
        inner = [h for h in self.host if h.start_ns <= t <= h.end_ns]
        what = max(inner, key=lambda h: h.start_ns).name if inner \
            else "submit"
        return f"q{reqs[0].qid} {what}"

    def idle_gaps(self) -> list[tuple[str, float]]:
        """As ``Summary.idle_gaps``, a gap inside a request named by the
        innermost ``serve.*`` span around it: ``q5 serve.fetch``."""
        s = self.summary
        cuts = sorted({t for sp in list(s.spans) + list(self.host)
                       for t in (sp.start_ns, sp.end_ns)})
        gaps = []
        for d in s.devices:
            edges = [s.start_ns]
            for a, b in s.busy_intervals(d):
                edges += [a, b]
            edges.append(s.end_ns)
            for a, b in zip(edges[::2], edges[1::2]):
                points = [a] + [t for t in cuts if a < t < b] + [b]
                for lo, hi in zip(points, points[1:]):
                    gaps.append((self._label((lo + hi) / 2), (hi - lo) / 1e9))
        return sorted((g for g in gaps if g[1] > 0), key=lambda g: -g[1])
