"""Device self time of the ``rel.compact`` operator scope (``compact``, front
compaction) in the traced pass, summed over the chips."""
LAYER, UNIT, MOVES = "relational ops (core/relational.py)", "ms", "pass_s"
SCOPE = "rel.compact"


def read(run):
    t = run.trace
    if t is None or not any(o.scope == SCOPE for o in t.ops):
        return None
    return 1e3 * t.scope_s(SCOPE)
