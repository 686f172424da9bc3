"""Share of the traced pass in which no operation ran on the device: 1 less
the union of the device operations' intervals over the pass, averaged over
the chips."""
LAYER, UNIT, MOVES = "device (TPU v5e)", "%", "query_geomean_ms"


def read(run):
    t = run.trace
    if t is None or not t.ops:
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s)
