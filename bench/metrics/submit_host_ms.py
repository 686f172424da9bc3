"""Host share of a request: per request of the traced pass, the length of
the benchmark's ``bench.submit q<N>`` span around ``QueryServer.submit`` less the
device-busy time inside it, averaged over the pass's requests."""
LAYER, UNIT, MOVES = "serving (serve/server.py)", "ms", "query_geomean_ms"


def read(run):
    t = run.trace
    if t is None or not t.ops:
        return None
    host = [(s.end_ns - s.start_ns) / 1e9 - t.busy_within(s) for s in t.spans]
    return 1e3 * sum(host) / len(host)
