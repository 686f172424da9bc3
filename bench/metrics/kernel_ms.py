"""Device time of the Pallas kernels (``tpu_custom_call``) in the traced
pass, summed over the chips."""
LAYER, UNIT, MOVES = "kernels (kernels/*)", "ms", "query_geomean_ms"


def read(run):
    t = run.trace
    if t is None or not any(o.cls == "kernel" for o in t.ops):
        return None
    return 1e3 * t.class_s("kernel")
