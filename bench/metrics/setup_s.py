"""Process start to window start: data generation, upload, loading (or
compiling) every program, and the warm pass."""
LAYER, UNIT, MOVES = None, "s", None


def read(run):
    return run.setup_s
