"""The control of the comparison that decides ``correct``: the plain
reference computed in float32, the precision below the float64 that the
configuration states, put in the program's place.  The comparison has to
reject it.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--passes 3]

For each seed it makes the cell's tables and the window's requests as a run
of ``bench/run.py`` would, answers them with the float32 reference, compares
those answers with the float64 reference, and prints one JSON line: the
numbers compared, with their limits, and ``correct``.  It needs no chip; the
benchmark's own runs never run it.
"""
import argparse
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def control_result(root: str, workload: str, seed: int, passes: int) -> dict:
    from harness import check, datagen
    from harness.client import Execution
    from harness.spec import Bench
    from harness.traffic import WINDOW_STREAM

    cell = Bench(root).cell(workload)
    conf = cell.config
    data = datagen.generate(conf["scale_factor"], seed, skew=conf["skew"],
                            hot_share=conf["hot_share"])
    stream = cell.traffic.passes(seed, WINDOW_STREAM)
    executions = [Execution(r.qid, r.params, 0.0, 0.0, None)
                  for _ in range(passes) for r in next(stream)]

    def float32(ex):
        return check.reference(conf["reference"], ex.qid)(
            data, ex.params, np.float32)

    numbers, where = check.check(executions, data, conf["reference"],
                                 cell.traffic.limits, answers=float32)
    return {"workload": workload, "seed": seed,
            "correct": check.passed(numbers), "attempted": len(executions),
            "worst": where, "check": numbers}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--passes", type=int, default=3)
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH)
    for seed in args.seeds.split(","):
        print(json.dumps(control_result(ROOT, args.workload, int(seed),
                                        args.passes)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
