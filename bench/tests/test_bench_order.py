"""The run's seed does not change the work: every run of a cell sends the
same queries in the same rounds and in the same order, so each round reads,
touches and places on processors the same rows and gives the same answers.

The order decides which queries share a processor, and each processor pools
the hub chains of the queries it holds, so an order drawn from the run's seed
would change a round's reads and its time from seed to seed.
"""

import json

import numpy as np

from bench.tests.helpers import run_child

SEEDS = (2**31 + 11, 2**31 + 12)
FIELDS = ("reads", "touched", "per_proc", "counts", "nodes")

CHILD = """
import json
import numpy as np
from bench import run

orig, served = run.closed_loop, {}

def keep(*args, **kwargs):
    rounds, t0, t1 = orig(*args, **kwargs)
    served["rounds"] = rounds
    return rounds, t0, t1

run.closed_loop = keep
for seed in %(seeds)r:
    run.main(["--workload", "web3hop.hotspot", "--seed", str(seed), "--seconds", "2",
              "--trace", "0", "--rehearse"])
    print("ROUNDS", seed, json.dumps([{k: np.asarray(r[k]).tolist() for k in %(fields)r}
                                      for r in served["rounds"]]), flush=True)
"""


def test_the_runs_seed_leaves_every_rounds_work_as_it_is(tmp_path):
    rc, out, err = run_child(CHILD % {"seeds": SEEDS, "fields": FIELDS}, tmp_path)
    assert rc == 0, err[-3000:]
    served = {int(line.split(" ", 2)[1]): json.loads(line.split(" ", 2)[2])
              for line in out if line.startswith("ROUNDS ")}
    a, b = (served[s] for s in SEEDS)
    assert a and b
    differ = [(i, k) for i, (ra, rb) in enumerate(zip(a, b)) for k in FIELDS
              if not np.array_equal(ra[k], rb[k])]
    assert not differ, f"(round, field) that differ between seeds: {differ}"
