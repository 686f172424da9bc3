"""Set-up compiles in the cell's order and refuses a program that does not
span the cell's chips, garbage collections in the window are counted, and
the check names where its largest error lies."""
import gc
import os
import subprocess
import sys

import jax
import numpy as np

from benchkit import BENCH, ROOT
from harness import check
from harness.client import Client, Execution, GcPauses


def test_set_up_compiles_in_the_cells_order():
    program = jax.jit(lambda x: x + 1).lower(np.zeros(4)).compile()

    class Server:
        order = []

        def compiled(self, qid):
            self.order.append(qid)
            return program

    client = Client.__new__(Client)
    client.server = Server()
    client.devices = jax.devices()[:1]
    client.prepare((14, 6, 1, 19, 12))
    assert Server.order == [14, 6, 1, 19, 12]


GUARD = """
import sys
import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
sys.path[:0] = [sys.argv[1], sys.argv[2]]
from harness.client import Client
from harness.device import NoChip

devices = jax.devices()
assert len(devices) == 4, devices
mesh = jax.make_mesh((4,), ("data",), devices=devices)
wide = jax.jit(lambda x: x * 2, in_shardings=NamedSharding(
    mesh, P("data"))).lower(np.zeros(8)).compile()
narrow = jax.jit(lambda x: x * 2).lower(np.zeros(8)).compile()


class Server:
    def compiled(self, qid):
        return {18: wide, 3: wide, 10: narrow}[qid]


client = Client.__new__(Client)
client.server = Server()
client.devices = devices
client.prepare((18, 3))
print("wide passes")
try:
    client.prepare((18, 3, 10))
except NoChip as e:
    print(f"NoChip: {e}")
client.devices = devices[:1]
client.prepare((10,))
print("one chip passes")
try:
    client.prepare((10, 18))
except NoChip as e:
    print(f"NoChip: {e}")
"""


def test_a_program_must_span_exactly_the_cells_chips():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", GUARD, BENCH, os.path.join(ROOT, "src")],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.splitlines() == [
        "wide passes",
        "NoChip: q10 runs on 1 of the cell's 4 chips",
        "one chip passes",
        "NoChip: q18 runs on 1 of the cell's 1 chips and on 3 others"]


def test_a_collection_in_the_block_is_counted():
    with GcPauses() as pauses:
        gc.collect()
    assert len(pauses.pauses) == 1 and pauses.pauses[0] >= 0.0
    gc.collect()                     # outside the block: not counted
    assert len(pauses.pauses) == 1


def test_the_check_names_the_query_and_column_of_its_worst_error(
        monkeypatch):
    want = {6: {"revenue": np.array([100.0])},
            1: {"flag": np.array([1, 2]), "sum_qty": np.array([10.0, 20.0]),
                "avg": np.array([4.0, 8.0])}}
    monkeypatch.setattr(check, "reference",
                        lambda name, qid: lambda data, params, ft: want[qid])
    got = {6: {"revenue": np.array([100.0 * (1 + 1e-12)])},
           1: {"flag": np.array([1, 2]), "sum_qty": np.array([10.0, 20.0]),
               "avg": np.array([4.0, 8.0 * (1 + 1e-9)])}}
    runs = [Execution(q, {}, 0.0, 0.0, got[q]) for q in (6, 1)]
    limits = {"answers_wrong": 0, "max_rel_err": 1e-10}
    numbers, where = check.check(runs, None, "tpch", limits)
    assert where == "q1 avg"
    assert numbers["answers_wrong"]["value"] == 0
    assert 0.9e-9 < numbers["max_rel_err"]["value"] < 1.1e-9
    assert not check.passed(numbers)
