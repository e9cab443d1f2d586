#!/usr/bin/env python3
"""Run a cell with a guarantee broken underneath (``faulty_server.py``)
at the cell's own size, once a seed, and print what the comparison read.

    python3 cellbench/tests/control.py --workload <cell> --fault <name>
                                       --seeds 1,2,3 [--seconds 10]

Every run has to come out ``correct: false``; the exit code is 0 only
then. The benchmark's own runs never start this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from cellbench import run_cell    # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    child = [os.path.join(HERE, "faulty_server.py"), "--fault", args.fault]
    caught = True
    for seed in (int(s) for s in args.seeds.split(",")):
        result = run_cell.run(args.workload, seed, args.seconds, False,
                              child=child)
        print(json.dumps({"workload": args.workload, "fault": args.fault,
                          "seed": seed, "correct": result["correct"],
                          "attempted": result["attempted"],
                          "checked": result["checked"],
                          "compared": result["compared"]}), flush=True)
        caught = caught and not result["correct"]
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
