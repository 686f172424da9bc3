"""Sum over the cell's query types of each type's mean latency in the
window: the wall time of one pass, as a dashboard that refreshes the set
feels it.  Long queries dominate it."""
from harness.client import mean_latency_s

LAYER, UNIT, MOVES = None, "s", None


def read(run):
    return sum(mean_latency_s(run.executions).values())
