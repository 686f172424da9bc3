"""Profiler trace -> device time by class of operation and by operator
scope, idle gaps, spans.

A traced run profiles one pass.  The reduction reads the ``.xplane.pb`` the
profiler writes, with nothing but JAX:

* device operations: the events of every TPU plane's ``XLA Ops`` line, one
  plane per chip, each charged to the request whose program execution (an
  ``XLA Modules`` event of its chip) holds it;
* host spans: the benchmark's ``bench.submit q<N>`` annotations around each
  request, on the same clock as far as the trace aligns it.

Each device operation is classed by HLO opcode, taken from the optimized HLO
text of the program the request ran (``QueryServer.compiled``), never from
its name: a fusion, or an async wrapper, takes the classes of the
instructions it calls.  Classes: ``kernel`` (a Pallas ``tpu_custom_call``),
``sort``, ``collective`` (an exchange between chips: ``all-to-all``,
``all-gather``, ``all-reduce``, ``reduce-scatter``, ``collective-permute``,
or their ``-start``/``-done`` halves), ``gather_scatter``, ``other``; an
operation that holds several counts under the first of that order.

Each operation also gets the engine's operator scope (``Op.scope``): the
innermost ``rel.*`` component of the ``op_name`` metadata its HLO
instruction carries (``repro.core.tracing`` wraps each relational operator
in a ``jax.named_scope``), None outside every scope; a fusion without
metadata takes its fused root's.  Every operation has one scope or none, so
the scopes' device times and the unscoped time add up to the pass's.

Times of several chips add up: ``class_s`` and ``scope_s`` sum device
seconds over the chips, ``busy_s`` and ``busy_within`` average them.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import re

CLASSES = ("kernel", "sort", "collective", "gather_scatter", "other")
COLLECTIVES = frozenset(
    f"{op}{half}" for op in ("all-to-all", "all-gather", "all-reduce",
                             "reduce-scatter", "collective-permute")
    for half in ("", "-start", "-done"))
SPAN = re.compile(r"^bench\.submit q(\d+)$")
OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
SCOPE = re.compile(r"(?:^|/)(rel\.[A-Za-z_]+)(?=/|$)")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9\-]*)\(")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition|branch_computations)"
                    r"=\{?%?([\w.\-]+)")


# instructions whose class and scope come from the computations they call
_CALLERS = frozenset({"fusion", "async-start", "async-update", "async-done"})


def _instructions(text: str):
    """(computation, instruction name, the rest of its line, whether it is
    the computation's ROOT) for every instruction of an HLO module."""
    current = None
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m and current is not None:
            yield (current, m.group(1), m.group(2),
                   line.lstrip().startswith("ROOT"))
            continue
        m = _COMP.match(line)
        if m and "=" not in line.split("{")[0]:
            current = m.group(1)


def _opcode(rest: str) -> str:
    op = _OPCODE.search(rest)
    opcode = op.group(1) if op else ""
    if opcode == "custom-call" and "tpu_custom_call" in rest:
        return "tpu_custom_call"
    return opcode


def hlo_classes(text: str) -> dict[str, str]:
    """Instruction name -> class, for every instruction of an HLO module."""
    comps: dict[str, list[tuple[str, str, list[str]]]] = {}
    for comp, name, rest, _ in _instructions(text):
        opcode = _opcode(rest)
        called = _CALLS.findall(rest) if opcode in _CALLERS else []
        comps.setdefault(comp, []).append((name, opcode, called))

    def opcodes(comp: str, seen: set) -> set[str]:
        out = set()
        for _, opcode, called in comps.get(comp, []):
            out.add(opcode)
            for c in called:
                if c not in seen:
                    seen.add(c)
                    out |= opcodes(c, seen)
        return out

    classes = {}
    for comp in comps.values():
        for name, opcode, called in comp:
            ops = {opcode}
            for c in called:
                ops |= opcodes(c, {c})
            classes[name] = classify(ops)
    return classes


def classify(opcodes: set[str]) -> str:
    if "tpu_custom_call" in opcodes:
        return "kernel"
    if "sort" in opcodes:
        return "sort"
    if opcodes & COLLECTIVES:
        return "collective"
    if opcodes & {"gather", "scatter"}:
        return "gather_scatter"
    return "other"


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
    for comp, name, rest, root in _instructions(text):
        meta = OP_NAME.search(rest)
        own[name] = meta.group(1) if meta else None
        if _opcode(rest) in _CALLERS:
            calls[name] = _CALLS.findall(rest)
        if root:
            roots[comp] = name

    def scope(name: str) -> str | None:
        if own.get(name) is not None:
            return scope_of(own[name])
        for comp in calls.get(name, [])[:1]:   # a fusion: its fused root's
            if comp in roots:
                return scope(roots[comp])
        return None

    return {name: scope(name) for name in own}


@dataclasses.dataclass(frozen=True)
class Op:
    """A device operation; ``self_ns`` leaves out the operations nested in
    it (a ``while`` loop's events span its body's)."""
    name: str
    qid: int | None
    cls: str
    start_ns: float
    end_ns: float
    device: str
    self_ns: float | None = None
    scope: str | None = None

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns if self.self_ns is None \
            else self.self_ns


def _self_times(events: list[tuple[float, float]]) -> list[float]:
    """Each interval's length less that of the intervals directly nested in
    it (events of one line nest or are disjoint)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    own = [b - a for a, b in events]
    stack: list[int] = []
    for i in order:
        a, b = events[i]
        while stack and events[stack[-1]][1] <= a:
            stack.pop()
        if stack and b <= events[stack[-1]][1]:
            own[stack[-1]] -= b - a
        stack.append(i)
    return own


@dataclasses.dataclass(frozen=True)
class Span:
    qid: int
    start_ns: float
    end_ns: float


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


@dataclasses.dataclass
class Summary:
    """The reduced trace of one traced pass."""
    ops: list[Op]
    spans: list[Span]

    @property
    def start_ns(self) -> float:
        return min(s.start_ns for s in self.spans)

    @property
    def end_ns(self) -> float:
        return max(s.end_ns for s in self.spans)

    @property
    def window_s(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    @property
    def devices(self) -> list[str]:
        return sorted({o.device for o in self.ops})

    def busy_intervals(self, dev: str) -> list[tuple[float, float]]:
        lo, hi = self.start_ns, self.end_ns
        return _union([(max(o.start_ns, lo), min(o.end_ns, hi))
                       for o in self.ops
                       if o.device == dev and o.end_ns > lo
                       and o.start_ns < hi])

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        devs = self.devices
        if not devs:
            return 0.0
        return sum(b - a for d in devs for a, b in self.busy_intervals(d)) \
            / len(devs) / 1e9

    def class_s(self, cls: str) -> float:
        """Device seconds of one class of operation, summed over devices."""
        return sum(o.dur_ns for o in self.ops if o.cls == cls) / 1e9

    def scope_s(self, scope: str | None) -> float:
        """Device seconds (self time) of the operations in ``scope``, summed
        over devices; ``None`` gives the operations outside every scope."""
        return sum(o.dur_ns for o in self.ops if o.scope == scope) / 1e9

    def busy_within(self, span: Span) -> float:
        """Device-busy seconds inside one host span, averaged over devices."""
        devs = self.devices
        tot = 0.0
        for d in devs:
            for a, b in self.busy_intervals(d):
                tot += max(0.0, min(b, span.end_ns) - max(a, span.start_ns))
        return tot / max(1, len(devs)) / 1e9

    def idle_gaps(self) -> list[tuple[str, float]]:
        """Gaps in the device's busy time, cut at the host spans' ends and
        named by what the host was doing: ``q<N> submit`` inside a request's
        span, ``between requests`` outside any."""
        cuts = sorted({t for s in self.spans for t in (s.start_ns, s.end_ns)})
        gaps = []
        for d in self.devices:
            edges = [self.start_ns]
            for a, b in self.busy_intervals(d):
                edges += [a, b]
            edges.append(self.end_ns)
            for a, b in zip(edges[::2], edges[1::2]):
                points = [a] + [t for t in cuts if a < t < b] + [b]
                for lo, hi in zip(points, points[1:]):
                    mid = (lo + hi) / 2
                    inside = [s for s in self.spans
                              if s.start_ns <= mid <= s.end_ns]
                    label = f"q{inside[0].qid} submit" if inside \
                        else "between requests"
                    gaps.append((label, (hi - lo) / 1e9))
        return sorted((g for g in gaps if g[1] > 0), key=lambda g: -g[1])

    def breakdown(self) -> dict:
        """The ten device operations that took most time, each keyed with
        its scope (``q3 rel.join_probe fusion.99 (gather_scatter)``), and the
        ten longest idle gaps."""
        by_op: dict[str, float] = {}
        for o in self.ops:
            key = " ".join(x for x in (f"q{o.qid}", o.scope, o.name) if x) \
                + f" ({o.cls})"
            by_op[key] = by_op.get(key, 0.0) + o.dur_ns / 1e9
        top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps()[:10]]}


@contextlib.contextmanager
def capture(logdir: str):
    """Profile the block into ``logdir``.  The Python tracer stays off: it
    would time every Python call of the host path and inflate the spans."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def programs(server, qids) -> dict[int, str]:
    """Optimized HLO text of the program each query runs."""
    return {q: server.compiled(q).as_text() for q in qids}


def load(logdir: str, hlo: dict[int, str]) -> Summary:
    """Reduce the trace under ``logdir`` (one ``.xplane.pb``)."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"{len(files)} traces under {logdir}")
    return reduce(ProfileData.from_file(files[0]), hlo)


def _offset(spans: list[Span], modules: list[tuple[float, float]]) -> float:
    """Device clock less host clock, as far as the trace shows it.

    Every program of the traced pass is dispatched inside a request's span
    and has finished when the span ends, so each program execution (an
    ``XLA Modules`` event) bounds the offset from both sides against the
    span nearest to it.  The profiler aligns the clocks to within about a
    millisecond: the offset taken is 0 where 0 is consistent with every
    bound, else the consistent value nearest to 0.
    """
    lo, hi = -float("inf"), float("inf")
    for a, b in modules:
        s = min(spans, key=lambda s: max(s.start_ns - a, a - s.end_ns, 0))
        lo, hi = max(lo, b - s.end_ns), min(hi, a - s.start_ns)
    return max(lo, min(0.0, hi)) if lo <= hi else 0.0


def _owners(spans: list[Span], modules: list[tuple[float, float]],
            shift: float) -> list[Span | None]:
    """The request span each program execution ran in: the one it overlaps
    most once the clocks are aligned (None where it overlaps none)."""
    out = []
    for a, b in modules:
        a, b = a - shift, b - shift
        best, most = None, 0.0
        for s in spans:
            overlap = min(b, s.end_ns) - max(a, s.start_ns)
            if overlap > most:
                best, most = s, overlap
        out.append(best)
    return out


def reduce(profile, hlo: dict[int, str]) -> Summary:
    """Each device operation is charged to the request whose program
    execution (``XLA Modules`` event, on the operation's own chip and clock)
    holds it; an operation outside every execution, to the request span
    that holds its start."""
    classes = {q: hlo_classes(text) for q, text in hlo.items()}
    scopes = {q: hlo_scopes(text) for q, text in hlo.items()}
    spans = []
    raw = []
    modules: dict[str, list[tuple[float, float]]] = {}
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    m = SPAN.match(ev.name)
                    if m:
                        spans.append(Span(int(m.group(1)), ev.start_ns,
                                          ev.end_ns))
        elif re.match(r"^/device:TPU:\d+$", plane.name):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules[plane.name] = sorted(
                        (ev.start_ns, ev.end_ns) for ev in line.events)
                if line.name == "XLA Ops":
                    evs = [(ev.name, ev.start_ns, ev.end_ns)
                           for ev in line.events]
                    own = _self_times([(a, b) for _, a, b in evs])
                    raw += [(n, a, b, plane.name, o)
                            for (n, a, b), o in zip(evs, own)]
    spans.sort(key=lambda s: s.start_ns)
    everything = [m for ms in modules.values() for m in ms]
    shift = _offset(spans, everything) if spans else 0.0
    owners = {dev: _owners(spans, ms, shift) for dev, ms in modules.items()}
    starts = {dev: [a for a, _ in ms] for dev, ms in modules.items()}
    ops = []
    for text, a, b, dev, own in raw:
        i = bisect.bisect_right(starts.get(dev, []), a) - 1
        owner = owners[dev][i] if i >= 0 and a <= modules[dev][i][1] \
            else None
        a, b = a - shift, b - shift
        if owner is None:
            owner = next((s for s in spans if s.start_ns <= a <= s.end_ns),
                         None)
        qid = owner.qid if owner else None
        # an event is named by its HLO instruction: "%name = shape op(...)"
        m = _INSTR.match(text)
        name = m.group(1) if m else text
        cls = classes.get(qid, {}).get(name)
        if cls is None:           # not in the program's HLO: its own opcode
            op = _OPCODE.search(m.group(2)) if m else None
            cls = classify({op.group(1)} if op else set())
        ops.append(Op(name, qid, cls, a, b, dev, own,
                      scopes.get(qid, {}).get(name)))
    return Summary(ops, spans)
