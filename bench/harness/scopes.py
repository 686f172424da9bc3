"""The engine's own tracing in a profiler trace: operator scopes and the
host spans of ``QueryServer.submit``.

The engine (``repro.core.tracing``) wraps each relational operator in a
``jax.named_scope`` named ``rel.<operator>`` and each phase of ``submit`` in
a ``TraceAnnotation`` named ``serve.<phase>``.  This module reads both, with
nothing but JAX, on top of :mod:`harness.trace`'s reduction:

* a device operation's scope is the innermost ``rel.*`` component of the
  ``op_name`` metadata its HLO instruction carries (None outside every
  scope); a fusion without metadata takes its fused root's.  Every
  operation has one scope or none, so the scopes' device times and the
  unscoped time add up to the pass's device time;
* host spans are the ``serve.*`` events of the host planes, on the clock
  of the benchmark's ``bench.submit q<N>`` spans (one thread writes both).
"""
from __future__ import annotations

import dataclasses
import re

from . import trace as tr

OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
SCOPE = re.compile(r"(?:^|/)(rel\.[A-Za-z_]+)(?=/|$)")
HOST_SPAN = "serve."
# the names the readers below are given (``repro.core.tracing`` defines
# them; a test checks that it still does)
LAUNCH = ("serve.bind", "serve.lookup", "serve.dispatch")
FETCH = ("serve.fetch",)
METRIC_SCOPES = {"join_probe_ms": "rel.join_probe",
                 "join_take_ms": "rel.join_take",
                 "join_build_ms": "rel.join_build",
                 "group_by_ms": "rel.group_by",
                 "compact_ms": "rel.compact",
                 "unscoped_ms": None}


def scope_of(op_name: str) -> str | None:
    """The innermost ``rel.*`` component of an ``op_name``."""
    found = SCOPE.findall(op_name)
    return found[-1] if found else None


def hlo_scopes(text: str) -> dict[str, str | None]:
    """Instruction name -> operator scope, for every instruction of an HLO
    module."""
    own: dict[str, str | None] = {}     # None: the instruction has no op_name
    calls: dict[str, list[str]] = {}
    roots: dict[str, str] = {}          # computation -> its ROOT instruction
    current = None
    for line in text.splitlines():
        m = tr._INSTR.match(line)
        if m and current is not None:
            name, rest = m.groups()
            meta = OP_NAME.search(rest)
            own[name] = meta.group(1) if meta else None
            op = tr._OPCODE.search(rest)
            if op and op.group(1) == "fusion":
                calls[name] = tr._CALLS.findall(rest)
            if line.lstrip().startswith("ROOT"):
                roots[current] = name
            continue
        m = tr._COMP.match(line)
        if m and "=" not in line.split("{")[0]:
            current = m.group(1)

    def scope(name: str) -> str | None:
        if own.get(name) is not None:
            return scope_of(own[name])
        for comp in calls.get(name, [])[:1]:   # a fusion: its fused root's
            if comp in roots:
                return scope(roots[comp])
        return None

    return {name: scope(name) for name in own}


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
    """A reduced trace, its operations' scopes and the engine's host spans."""
    summary: tr.Summary
    scopes: list[str | None]          # one per ``summary.ops``
    host: list[HostSpan]

    @classmethod
    def of(cls, summary: tr.Summary, hlo: dict[int, str],
           profile) -> "Scoped":
        by_query = {q: hlo_scopes(text) for q, text in hlo.items()}
        scopes = [by_query.get(o.qid, {}).get(o.name) for o in summary.ops]
        return cls(summary, scopes, host_spans(profile))

    def scope_s(self, scope: str | None) -> float:
        """Device seconds (self time) of the operations in ``scope``;
        ``None`` gives the operations outside every scope."""
        return sum(o.dur_ns for o, s in zip(self.summary.ops, self.scopes)
                   if s == scope) / 1e9

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

    def breakdown(self) -> dict:
        """As ``Summary.breakdown``, each operation keyed with its scope:
        ``q3 rel.join_probe fusion.99 (gather_scatter)``."""
        by_op: dict[str, float] = {}
        for o, scope in zip(self.summary.ops, self.scopes):
            key = " ".join(x for x in (f"q{o.qid}", scope, o.name) if x) \
                + f" ({o.cls})"
            by_op[key] = by_op.get(key, 0.0) + o.dur_ns / 1e9
        top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps()[:10]]}

