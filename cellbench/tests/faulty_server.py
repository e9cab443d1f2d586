#!/usr/bin/env python3
"""The server child with one guarantee broken underneath the timed path.

    faulty_server.py --fault <name> <control dir> <repo root> -d ... --bind ...

``answer_plus_one``  every 7th Count answer is one too large where the
                     executor produces it (a fault: an answer altered)
``drop_slice``       reads leave out the index's last slice: an
                     approximate answer where the configuration says exact
                     (the control of the read-only cells)
``lose_write``       a SetBit is acknowledged and never applied: an
                     acknowledged write that no later read shows (the
                     control of the read/write cell)

Used by the tests here and by ``control.py`` on the chip; no benchmark
run starts it.
"""

from __future__ import annotations

import itertools
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def plant(fault: str) -> None:
    from pilosa_tpu import executor as ex
    original = ex.Executor.execute
    calls = itertools.count(1)

    def names(query) -> list[str]:
        if isinstance(query, str):
            query = ex.parse_pql(query)
        return [c.name for c in query.calls]

    def execute(self, index, query, slices=None, opt=None, **kw):
        kinds = names(query)
        if fault == "lose_write" and kinds == ["SetBit"]:
            return [True]
        if fault == "drop_slice" and not slices and "Count" in kinds:
            last = self.holder.index(index).max_slice()
            slices = list(range(last)) or [0]
        results = original(self, index, query, slices, opt, **kw)
        if fault == "answer_plus_one" and kinds == ["Count"] \
                and next(calls) % 7 == 0:
            results = [results[0] + 1]
        return results

    if fault not in ("answer_plus_one", "drop_slice", "lose_write"):
        raise SystemExit(f"unknown fault {fault!r}")
    ex.Executor.execute = execute


def main(argv: list[str]) -> int:
    if argv[0] != "--fault":
        raise SystemExit(__doc__)
    fault, rest = argv[1], argv[2:]
    sys.path.insert(0, rest[1])
    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "lib"))
    plant(fault)
    import traced_server
    return traced_server.main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
