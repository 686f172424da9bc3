"""The one traffic generator: a traffic file's parameters -> requests.

A traffic file (``bench/traffic/<name>.json``) names the query templates of
a cell in the order a pass runs them, for each parameterized template the
rule that draws its substitution parameters (TPC-H §2.4), and the limits of
the comparison that decides ``correct`` (:mod:`harness.check`), which depend
on the aggregates its queries compute.  One client runs passes back to back,
as TPC-H's power test runs its stream.

A rule has ``draw``, uniform integers ``[lo, hi]`` drawn per execution, and
``params``, each template parameter as ``[kind, draw, arg]``:

* ``days_before``: ``arg`` (an ISO date) minus the draw, in days;
* ``days_after``: ``arg`` plus the draw, in days;
* ``jan1``: January 1 of the year drawn plus ``arg``;
* ``hundredths``: (the draw plus ``arg``) / 100;
* ``int``: the draw plus ``arg``.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Iterator

import numpy as np

from .datagen import days

# what each rule kind makes of (draw, arg)
_KINDS = {
    "days_before": lambda v, arg: days(arg) - v,
    "days_after": lambda v, arg: days(arg) + v,
    "jan1": lambda v, arg: days(f"{v + arg}-01-01"),
    "hundredths": lambda v, arg: (v + arg) / 100,
    "int": lambda v, arg: v + arg,
}

# independent streams drawn from one seed
WARM_STREAM, WINDOW_STREAM = 1, 2


@dataclasses.dataclass(frozen=True)
class Request:
    qid: int
    params: dict


@dataclasses.dataclass(frozen=True)
class Traffic:
    queries: tuple[int, ...]
    rules: dict[int, dict]
    limits: dict[str, float]

    @classmethod
    def load(cls, path: str) -> "Traffic":
        with open(path) as f:
            spec = json.load(f)
        queries = tuple(int(q) for q in spec["queries"])
        rules = {int(q): r for q, r in spec.get("bindings", {}).items()}
        for qid, rule in rules.items():
            if qid not in queries:
                raise ValueError(f"{path}: a rule for q{qid}, which no pass "
                                 "runs")
            for pname, (kind, draw, _) in rule["params"].items():
                if kind not in _KINDS or draw not in rule["draw"]:
                    raise ValueError(f"{path}: q{qid} {pname}: unknown kind "
                                     f"{kind!r} or draw {draw!r}")
        return cls(queries, rules, dict(spec["limits"]))

    def bind(self, qid: int, rng: np.random.Generator) -> dict:
        """One execution's parameters ({} for a literal query)."""
        rule = self.rules.get(qid)
        if rule is None:
            return {}
        drawn = {k: int(rng.integers(lo, hi + 1))
                 for k, (lo, hi) in sorted(rule["draw"].items())}
        return {p: _KINDS[kind](drawn[draw], arg)
                for p, (kind, draw, arg) in sorted(rule["params"].items())}

    def passes(self, seed: int, stream: int) -> Iterator[list[Request]]:
        """Passes of the cell's queries, each with fresh parameters."""
        rng = np.random.default_rng(np.random.SeedSequence([seed, stream]))
        while True:
            yield [Request(q, self.bind(q, rng)) for q in self.queries]
