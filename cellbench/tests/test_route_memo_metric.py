"""``route_memo_hit_pct`` (PR 26): the reader on a hand-made ``Run``
(the window's delta of ``/debug/vars.routeMemo``; None where the
counter is absent, as on a program without the route memo, and where no
read was routed), and the metric's file agreeing with its
``BENCHMARK.json`` entry."""

from __future__ import annotations

import importlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from cellbench import run_cell                                # noqa: E402

METRIC = "route_memo_hit_pct"

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
with open(os.path.join(ROOT, "cellbench", "metrics",
                       METRIC + ".json")) as _f:
    SPEC = json.load(_f)

read = importlib.import_module(
    "cellbench.readers." + SPEC["reader"]).read


def _run(before, after):
    run = run_cell.Run()
    run.before = {"status": {}, "vars": dict(before)}
    run.after = {"status": {}, "vars": dict(after)}
    return run


def _memo(hits, misses, invalidated=0):
    return {"routeMemo": {"hits": hits, "misses": misses,
                          "invalidated": invalidated}}


@pytest.mark.parametrize("before, after, want", [
    # the warm-up's misses are not the window's
    (_memo(380, 20), _memo(380 + 990, 20 + 10), 99.0),
    (_memo(0, 7), _memo(8000, 7), 100.0),
    (_memo(5, 5, 3), _memo(5, 105, 103), 0.0),
], ids=["99", "100", "0"])
def test_reader_reads_the_windows_delta(before, after, want):
    assert read(_run(before, after)) == pytest.approx(want)


@pytest.mark.parametrize("before, after", [
    ({}, {}),                           # the parent: no such counter
    ({}, _memo(10, 0)),                 # counter appeared mid-run
    (_memo(10, 2), _memo(10, 2)),       # no read was routed
], ids=["absent", "half", "idle"])
def test_reader_is_silent_where_there_is_nothing_to_read(before, after):
    assert read(_run(before, after)) is None


def test_reader_is_silent_on_an_untraced_run():
    run = run_cell.Run()
    run.before = run.after = None
    assert read(run) is None


def test_metric_file_agrees_with_benchmark_json():
    entry = next(m for m in BENCH["per_layer"] if m["name"] == METRIC)
    for k, v in entry.items():
        assert k == "workloads" or SPEC[k] == v, k
    assert entry["workloads"] == ["c4-count-hot", "c4-count-hot-solo"]
    assert entry["layer"] == "executor + routing"
    assert entry["moves"] == "read_p50_ms"
    assert entry["better"] == "higher" and entry["unit"] == "%"
    assert entry["source"] == "program_counter"
    assert BENCH["per_layer"][-1] is entry      # appended, not inserted
    assert SPEC["what"] and run_cell._reader(METRIC) is read
