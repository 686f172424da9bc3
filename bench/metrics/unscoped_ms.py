"""Device self time outside every ``rel.*`` operator scope (expressions,
filters, parameter converts) in the traced pass, summed over the chips."""
LAYER, UNIT, MOVES = "planner / plans (core/planner.py)", "ms", "pass_s"
SCOPE = None


def read(run):
    t = run.trace
    if t is None or not any(o.scope is SCOPE for o in t.ops):
        return None
    return 1e3 * t.scope_s(SCOPE)
