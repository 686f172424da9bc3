"""Device self time of the ``rel.order`` operator scope (``sort_by`` and
``sort_limit``: ORDER BY and LIMIT) in the traced pass, summed over the
chips."""
LAYER, UNIT, MOVES = "relational ops (core/relational.py)", "ms", "pass_s"
SCOPE = "rel.order"


def read(run):
    t = run.trace
    if t is None or not any(o.scope == SCOPE for o in t.ops):
        return None
    return 1e3 * t.scope_s(SCOPE)
