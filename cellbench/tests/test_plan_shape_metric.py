"""plan_shape_hit_pct: the reader on synthetic surfaces, and the metric
found BY NAME in BENCHMARK.json (not by its place in the list)."""

import json
import os

import pytest

from cellbench import run_cell
from cellbench.readers import plan_shape_hit

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run(before, after):
    run = run_cell.Run()
    run.before = None if before is None else {"status": {},
                                              "vars": dict(before)}
    run.after = None if after is None else {"status": {},
                                            "vars": dict(after)}
    return run


def _shapes(hits, misses, full):
    return {"planShapes": {"hits": hits, "misses": misses, "full": full}}


@pytest.mark.parametrize("before, after, want", [
    (_shapes(397, 3, 0), _shapes(397 + 30000, 3, 0), 100.0),
    # the warm-up's first sightings are not the window's
    (_shapes(0, 3, 0), _shapes(980, 13, 10), 98.0),
    (_shapes(5, 1, 0), _shapes(5, 1, 40), 0.0),
    ({}, {}, None),                         # the parent: no such counter
    ({}, _shapes(10, 0, 0), None),          # it appeared mid-run
    (_shapes(10, 2, 1), _shapes(10, 2, 1), None),   # nothing planned
    (None, None, None),                     # an untraced run
], ids=["100", "98", "0", "absent", "half", "idle", "untraced"])
def test_reader_reads_the_windows_delta(before, after, want):
    got = plan_shape_hit.read(_run(before, after))
    assert got == (pytest.approx(want) if want is not None else None)


def test_metric_is_declared_by_name_as_its_file_says():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(ROOT, "cellbench", "metrics",
                           "plan_shape_hit_pct.json")) as f:
        spec = json.load(f)
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "plan_shape_hit_pct")
    for k, v in entry.items():
        assert k == "workloads" or spec[k] == v, k
    assert run_cell._reader("plan_shape_hit_pct") is plan_shape_hit.read
    assert entry["workloads"] == ["c4-count-hot", "c4-count-hot-solo",
                                  "c4-count-hot-mesh4"]
    assert entry["moves"] == "read_p50_ms"
    assert entry["layer"] == "parse + plan"
    assert entry["source"] == "program_counter"
