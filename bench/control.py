#!/usr/bin/env python3
"""The benchmark's control on several seeds, in one process.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

The control is the program with its continuation chains capped at
`CHAIN_CAP` rows per hop, so hubs are expanded with part of their adjacency:
it breaks the configurations' guarantee that every frontier node is expanded
with its whole adjacency, the step that would make a round far cheaper. Each
of its runs prints its result line and must come out incorrect. The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run  # noqa: E402

CHAIN_CAP = 2


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    rc = 0
    for seed in args.seeds:
        print(f"control: seed={seed}", flush=True)
        rc |= run.main(["--workload", args.workload, "--seed", str(seed),
                        "--seconds", str(args.seconds), "--trace", "0"],
                       chain_cap=CHAIN_CAP)
    return rc


if __name__ == "__main__":
    sys.exit(main())
