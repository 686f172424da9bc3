"""Device time of the collectives (``all-to-all``, ``all-gather``,
``all-reduce``, ``reduce-scatter``, ``collective-permute``, their halves,
and fusions holding one) on the chips' ``XLA Ops`` lines in the traced
pass, summed over the chips."""
LAYER, UNIT, MOVES = "exchange / wire (core/exchange.py)", "ms", "pass_s"


def read(run):
    t = run.trace
    if t is None or not any(o.cls == "collective" for o in t.ops):
        return None
    return 1e3 * t.class_s("collective")
