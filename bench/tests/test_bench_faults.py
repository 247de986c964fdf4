"""A run with the timed path broken underneath comes out incorrect.

Each fault is planted under the harness, which runs as it does on the chip
apart from its look for one, at the rehearsal size:

- stale: the step returns what it returned before, so every round after
  the first hands back the answers of the round before it;
- half: half of each round's queries are left out;
- altered: one answer is altered where it is produced;
- exchange: on four devices, the all_to_all of the storage read is left
  out, so each chip reads its own shard whoever owns the row;
- uncounted: the storage-read counter reads 0 where rows were read;
- cold: one warm-up round too few, so the window compiles;
- control: the program with its continuation chains capped at
  `control.CHAIN_CAP` rows, the path `control.py` runs on the chip.

Each must fail the check named for it.
"""

import json

import pytest

from bench.control import CHAIN_CAP
from bench.tests.helpers import candidates_root, run_child

CAUGHT_BY = {
    "stale": "answers_wrong",
    "half": "queries_lost",
    "altered": "answers_wrong",
    "uncounted": "reads_rounds_out_of_range",
    "cold": "window_compiles",
    "control": "touched_rounds_wrong",
}
ONE_CHIP_FAULTS = tuple(CAUGHT_BY)

DRIVER = """
import json, sys
import numpy as np
from bench import run
from repro.serve import engine as E

orig = E.ServingEngine.run

def planted(kind):
    prev = {}
    def run_faulty(self, wl, state=None, drain=True):
        res, st = orig(self, wl, state=state, drain=drain)
        if kind == "stale":
            last, prev["counts"] = prev.get("counts"), res.counts.copy()
            if last is not None:
                res.counts = last
        elif kind == "half":
            h = res.counts.size // 2
            res.completed[h:] = False
            res.counts[h:] = -1
        elif kind == "altered":
            res.counts[0] += 1
        elif kind == "uncounted":
            res.reads = 0
        return res, st
    return run_faulty

for kind in %(faults)r:
    E.ServingEngine.run = orig if kind in ("control", "cold") else planted(kind)
    run.WARMUP_ROUNDS = 1 if kind == "cold" else 2
    print("FAULT", kind, flush=True)
    run.main(["--workload", "web3hop.hotspot", "--seed", "2147483659", "--seconds", "1",
              "--trace", "0", "--rehearse"], chain_cap=%(cap)r if kind == "control" else None)
"""

EXCHANGE = """
import sys
import jax.numpy as jnp
from bench import run
from repro.serve import engine as E

def local_only(ids, local_rows, local_deg, local_cont, owner_lut, loc_lut, **_):
    ok = ids >= 0
    l = loc_lut[jnp.maximum(ids, 0)]
    return (jnp.where(ok[:, None], local_rows[l], -1), jnp.where(ok, local_deg[l], 0),
            jnp.where(ok, local_cont[l], -1), ok)

E.sharded_multi_read = local_only
sys.exit(run.main(["--workload", "web3hop-x4.hotspot", "--seed", "2147483659",
                   "--seconds", "1", "--trace", "0", "--rehearse"], root=%(root)r))
"""


@pytest.fixture(scope="module")
def one_chip_results(tmp_path_factory):
    rc, out, err = run_child(DRIVER % {"faults": ONE_CHIP_FAULTS, "cap": CHAIN_CAP},
                             tmp_path_factory.mktemp("faults"))
    assert rc == 0, err[-3000:]
    results, kind = {}, None
    for line in out:
        if line.startswith("FAULT "):
            kind = line.split()[1]
        elif line.startswith("{"):
            results[kind] = json.loads(line)
    return results


@pytest.mark.parametrize("kind", ONE_CHIP_FAULTS)
def test_planted_fault_is_not_correct(kind, one_chip_results):
    result = one_chip_results[kind]
    assert result["correct"] is False
    assert result["checks"][CAUGHT_BY[kind]]["value"] > 0, result["checks"]


def test_exchange_left_out_is_not_correct(tmp_path):
    rc, out, err = run_child(EXCHANGE % {"root": candidates_root(tmp_path)}, tmp_path, chips=4)
    assert rc == 0, err[-3000:]
    result = json.loads(out[-1])
    assert result["correct"] is False
    assert result["checks"]["answers_wrong"]["value"] > 0
