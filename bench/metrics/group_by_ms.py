"""Device self time of the ``rel.group_by`` operator scope
(``group_aggregate``, every path) in the traced pass, summed over the
chips."""
LAYER, UNIT, MOVES = "relational ops (core/relational.py)", "ms", "pass_s"
SCOPE = "rel.group_by"


def read(run):
    t = run.trace
    if t is None or not any(o.scope == SCOPE for o in t.ops):
        return None
    return 1e3 * t.scope_s(SCOPE)
