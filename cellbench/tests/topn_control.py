#!/usr/bin/env python3
"""The control of the TopN cells: their server child with the index's
last slice left out of every TopN read (an approximate answer where the
configuration says exact), run at the cell's own size once a seed; every
run has to come out ``correct: false`` and the exit code is 0 only then.

    python3 cellbench/tests/topn_control.py --workload <cell>
                                            --seeds 1,2,3 [--seconds 10]

``faulty_server.py`` plants its faults in Count reads only; this file is
its TopN counterpart, and is also the child it starts:

    topn_control.py --child <control dir> <repo root> -d ... --bind ...

The benchmark's own runs never start it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def plant() -> None:
    from pilosa_tpu import executor as ex
    original = ex.Executor.execute

    def execute(self, index, query, slices=None, opt=None, **kw):
        if isinstance(query, str):
            query = ex.parse_pql(query)
        if not slices and any(c.name == "TopN" for c in query.calls):
            last = self.holder.index(index).max_slice()
            slices = list(range(last)) or [0]
        return original(self, index, query, slices, opt, **kw)

    ex.Executor.execute = execute


def child(argv: list[str]) -> int:
    sys.path.insert(0, argv[1])
    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "lib"))
    plant()
    import traced_server
    return traced_server.main(argv)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv[:1] == ["--child"]:
        return child(argv[1:])
    sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
    from cellbench import run_cell
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    caught = True
    for seed in (int(s) for s in args.seeds.split(",")):
        result = run_cell.run(args.workload, seed, args.seconds, False,
                              child=[os.path.abspath(__file__), "--child"])
        print(json.dumps({"workload": args.workload, "fault": "drop_slice",
                          "seed": seed, "correct": result["correct"],
                          "attempted": result["attempted"],
                          "checked": result["checked"],
                          "compared": result["compared"]}), flush=True)
        caught = caught and not result["correct"]
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
