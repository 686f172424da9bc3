"""Device self time of the ``rel.join_probe`` operator scope (``probe_index``:
the direct-address gather, ``searchsorted`` or the hash probe) in the
traced pass, summed over the chips."""
LAYER, UNIT, MOVES = "relational ops (core/relational.py)", "ms", "pass_s"
SCOPE = "rel.join_probe"


def read(run):
    t = run.trace
    if t is None or not any(o.scope == SCOPE for o in t.ops):
        return None
    return 1e3 * t.scope_s(SCOPE)
