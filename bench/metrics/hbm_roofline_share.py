"""The traced pass's share of the HBM roofline: the bytes of the TPC-H
columns each executed query must read at least once (``querybytes.json``,
from the spec's SQL, not from the program's plan), at the peak HBM
bandwidth (``peaks.json``) of the cell's chips together, over the traced
pass's length."""
LAYER, UNIT, MOVES = "device (TPU v5e)", "%", "pass_s"


def read(run):
    t = run.trace
    if t is None or not t.ops or run.peaks is None:
        return None
    need = sum(run.column_bytes[c] for ex in run.executions
               for c in run.query_columns[ex.qid])
    return 100.0 * need / (run.chips * run.peaks["hbm_bytes_per_s"]) \
        / t.window_s
