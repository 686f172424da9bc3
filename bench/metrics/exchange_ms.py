"""Device self time of the ``rel.exchange`` operator scope (shuffles,
broadcasts, final gathers and all-reduces between chips: pack, collective,
unpack) in the traced pass, summed over the chips."""
LAYER, UNIT, MOVES = "exchange / wire (core/exchange.py)", "ms", "pass_s"
SCOPE = "rel.exchange"


def read(run):
    t = run.trace
    if t is None or not any(o.scope == SCOPE for o in t.ops):
        return None
    return 1e3 * t.scope_s(SCOPE)
