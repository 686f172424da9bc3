"""Device time of HLO ``sort`` operations (and of fusions holding one) in
the traced pass, summed over the chips."""
LAYER, UNIT, MOVES = "relational ops (core/relational.py)", "ms", "pass_s"


def read(run):
    t = run.trace
    if t is None or not any(o.cls == "sort" for o in t.ops):
        return None
    return 1e3 * t.class_s("sort")
