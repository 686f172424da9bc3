"""One run of a cell on the CPU, with the timed path sound or broken.

    python rehearse.py <checkout> <workload> <seed> <mode> <trace>

``mode`` is ``sound``, ``alter`` (every answer's first float value is
altered where the server produces it), ``drop`` (half of every answer's
rows are left out) or ``control`` (the float32 reference answers in the
program's place).  Prints the result line as the harness makes it.
"""
import json
import os
import sys
import time

root, workload, seed, mode, trace = sys.argv[1:6]
sys.path.insert(0, os.path.join(root, "bench"))
sys.path.insert(0, os.path.join(root, "src"))

import jax  # noqa: E402

from harness import device, runner  # noqa: E402

# the CPU stands in for the chip, with no peaks to read
device.chips = lambda devices, count, peaks: (jax.devices()[:count], None)


def broken(submit):
    def run(self, template, bindings=None, **kw):
        out = submit(self, template, bindings, **kw)
        if mode == "alter":
            col = next(c for c, v in sorted(out.items()) if v.dtype.kind == "f")
            out[col] = out[col].copy()
            out[col][0] *= 1.0 + 1e-6
        elif mode == "drop":
            out = {c: v[: len(v) // 2] for c, v in out.items()}
        return out
    return run


if mode in ("alter", "drop"):
    from repro.serve.server import QueryServer
    QueryServer.submit = broken(QueryServer.submit)
if mode == "control":
    import control  # bench/control.py
    result = control.control_result(root, workload, int(seed), passes=1)
else:
    result = runner.run_cell(root, workload, int(seed), 0.2, trace == "1",
                             time.time())
print(json.dumps(result, default=float))
