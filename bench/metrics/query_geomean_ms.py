"""Geometric mean, over the cell's query types, of each type's mean latency
over all its executions in the window (``submit`` called to the NumPy answer
in hand): the statistic behind TPC-H's Power@Size.  Each query weighs the
same, so a gain on a short query shows."""
import math

from harness.client import mean_latency_s

LAYER, UNIT, MOVES = None, "ms", None


def read(run):
    means = mean_latency_s(run.executions).values()
    return 1e3 * math.exp(sum(math.log(m) for m in means) / len(means))
