"""The closed-loop client: set-up, the warm pass, the measured window.

One client sends one request at a time through ``QueryServer.submit`` with
the defaults users run (exact answers, default planner, sorted joins,
kernels chosen by the platform) and waits for the NumPy answer before it
sends the next.  Each request runs inside a host span, ``bench.submit
q<N>``, which the profiler records when a run is traced.  The server is as
wide as the cell (``devices=`` its chip count), and set-up refuses a
program that does not take its inputs on exactly the cell's chips.
"""
from __future__ import annotations

import dataclasses
import gc
import threading
import time
from typing import Iterable, Iterator

import jax
from jax import monitoring

from .device import check_spans
from .traffic import Request

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
_QUERY = threading.local()    # which query set-up is compiling


class CompileCounter:
    """Counts the XLA programs a block obtained: compiled, or read from the
    persistent cache; ``compiled_names`` names the ones compiled."""

    def __init__(self):
        self.obtained = 0
        self.cache_hits = 0
        self.compiled_names: list[str] = []
        self._lock = threading.Lock()     # events may come from any thread
        self._hit = threading.local()     # a hit precedes its program's end

    @property
    def compiled(self) -> int:
        return self.obtained - self.cache_hits

    def _duration(self, event: str, _secs: float, fun_name: str = "?",
                  **_kw) -> None:
        if event == BACKEND_COMPILE:
            hit = getattr(self._hit, "pending", False)
            self._hit.pending = False
            with self._lock:
                self.obtained += 1
                if not hit:
                    qid = getattr(_QUERY, "qid", None)
                    self.compiled_names.append(
                        fun_name if qid is None else f"q{qid} {fun_name}")

    def _event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT:
            self._hit.pending = True
            with self._lock:
                self.cache_hits += 1

    def __enter__(self) -> "CompileCounter":
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc) -> None:
        monitoring.unregister_event_duration_listener(self._duration)
        monitoring.unregister_event_listener(self._event)


class GcPauses:
    """The lengths of Python's garbage collections in a block, in seconds."""

    def __init__(self):
        self.pauses: list[float] = []
        self._t0 = 0.0

    def _callback(self, phase: str, _info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append(time.perf_counter() - self._t0)

    def __enter__(self) -> "GcPauses":
        gc.callbacks.append(self._callback)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._callback)


@dataclasses.dataclass
class Execution:
    """One request: what was asked, when, and what came back."""
    qid: int
    params: dict
    start: float
    end: float
    result: dict | None
    error: str | None = None

    @property
    def latency_s(self) -> float:
        return self.end - self.start


class Client:
    """One user of a ``QueryServer`` over the generated tables, served on
    ``devices``, the cell's chips."""

    def __init__(self, data, devices: list):
        from repro.core.table import Database
        from repro.serve.server import QueryServer
        self.devices = devices
        self.server = QueryServer(Database(data.tables, data.dicts,
                                           data.scale),
                                  devices=len(devices))

    def submit(self, req: Request) -> Execution:
        start = time.perf_counter()
        with jax.profiler.TraceAnnotation(f"bench.submit q{req.qid}"):
            try:
                result = self.server.submit(req.qid, req.params)
                error = None
            except Exception as e:  # an answer that never comes is recorded
                result, error = None, f"{type(e).__name__}: {e}"
        return Execution(req.qid, req.params, start, time.perf_counter(),
                         result, error)

    def prepare(self, qids) -> None:
        """Compile (or load) every program the cell runs, one after another
        in the cell's order, and refuse one that does not span the cell's
        chips (``device.NoChip``).  Programs that call one jitted function
        share its trace, with the source locations of whichever traced it
        first; on a TPU those locations are part of each Pallas kernel's
        payload, and so of the compilation cache's key.  A fixed order gives
        every process the same keys, so only a checkout's first run
        compiles."""
        for qid in qids:
            _QUERY.qid = qid
            check_spans(f"q{qid}", self.server.compiled(qid), self.devices)
        _QUERY.qid = None

    def counters(self) -> dict[str, int]:
        s = self.server
        return {"recompiles": s.recompiles, "overflow_reruns":
                s.overflow_reruns, "plan_cache_hits": s.cache_hits}

    def run_pass(self, requests: Iterable[Request]) -> list[Execution]:
        return [self.submit(r) for r in requests]

    def window(self, passes: Iterator[list[Request]], seconds: float,
               ) -> tuple[list[Execution], float]:
        """Closed loop until the first answer after ``seconds``, and at least
        one whole pass; returns the executions and the window's length."""
        done: list[Execution] = []
        t0 = time.perf_counter()
        for requests in passes:
            for req in requests:
                done.append(self.submit(req))
                if (done[-1].end - t0 >= seconds
                        and len(done) >= len(requests)):
                    return done, done[-1].end - t0
        raise AssertionError("passes ended")  # the generator is endless


def mean_latency_s(executions: list[Execution]) -> dict[int, float]:
    """Each query type's mean latency over all its executions."""
    by: dict[int, list[float]] = {}
    for ex in executions:
        by.setdefault(ex.qid, []).append(ex.latency_s)
    return {q: sum(v) / len(v) for q, v in by.items()}
