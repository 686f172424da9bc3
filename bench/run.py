"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``.  The run makes its tables and
requests from ``--seed``, sets up (tables on the chip, every program loaded
or compiled, one warm pass), then measures: with ``--trace 0`` a closed loop
of ``--seconds`` and the end-to-end metrics, with ``--trace 1`` one profiled
pass and the per-layer metrics.  It compares every answer with the plain
reference, prints the numbers compared with their limits as the last lines
of standard error, and one JSON result as the last line of standard output.
Without a TPU it exits non-zero and prints no result.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # libtpu writes its logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, BENCH)
    from harness.runner import run_cell
    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START)
    except Exception as e:  # no result line: the run measured nothing
        traceback.print_exc()
        print(f"run: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    for name, n in result["check"].items():
        print(f"check {name} {n['value']!r} limit {n['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
