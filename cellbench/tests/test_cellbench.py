"""The benchmark's own tests: run with ``pytest cellbench/tests``.

On the CPU, at 8 slices, through the Python entry (the command line
cannot cut the size): every cell of BENCHMARK.json runs end to end with
every answer equal to the reference; the command itself fails without a
TPU; the control and the planted fault come out ``correct: false``; the
trace reduction gives the known split on a recorded, trimmed trace; and
BENCHMARK.json keeps the contract's rules and resolves to files.
"""

from __future__ import annotations

import importlib
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from cellbench import run_cell                                # noqa: E402
from cellbench.lib import bytes_fns, check, trace_reduce      # noqa: E402
from cellbench.lib.data import Reference                      # noqa: E402
from cellbench.lib.loadgen import Record                      # noqa: E402
from cellbench.lib.traffic import Generator, Op, block_counts  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
FAULTY = [os.path.join(HERE, "faulty_server.py"), "--fault"]
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _json(*parts: str) -> dict:
    with open(os.path.join(ROOT, "cellbench", *parts)) as f:
        return json.load(f)


def _later_rw_cell() -> tuple[dict, dict, dict, dict]:
    """``share8-rw95``, kept out of BENCHMARK.json (PERF.md, Open
    questions) with its files ready: the bench it would be listed in,
    its entry, its configuration and its mix."""
    cell = {"name": "share8-rw95", "config": "baseline-c4-share8",
            "traffic": "rw95-zipf099", "chips": 1, "why": "see PERF.md"}
    bench = dict(BENCH)
    bench["end_to_end"] = BENCH["end_to_end"] + [
        {"name": "write_p95_ms", "unit": "ms", "better": "lower",
         "bound": 0.1, "source": "host_clock",
         "workloads": [cell["name"]]}]
    spec = _json("metrics", "wal_wait_ms.json")
    bench["per_layer"] = BENCH["per_layer"] + [
        {k: spec[k] for k in ("name", "unit", "better", "source", "layer",
                              "moves")} | {"workloads": [cell["name"]]}]
    return (bench, cell, _json("configs", cell["config"] + ".json"),
            _json("traffic", cell["traffic"] + ".json"))


def _listed(kind: str, cell: str) -> list[dict]:
    return [m for m in BENCH[kind]
            if "workloads" not in m or cell in m["workloads"]]


# -- BENCHMARK.json -----------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_keeps_the_contracts_rules():
    assert sorted(BENCH) == sorted(
        ["command", "paths", "run_seconds", "configs", "workloads",
         "end_to_end", "per_layer"])
    assert BENCH["paths"] == ["cellbench"]
    assert BENCH["command"] == ["python3", "cellbench/run_cell.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [w["traffic"] for w in BENCH["workloads"]]
             + [m["name"] for k in ("end_to_end", "per_layer")
                for m in BENCH[k]]
             + [k for c in BENCH["configs"] for k in c["reduced"]])
    for n in names:
        assert NAME.match(n), n
    metrics = [m["name"] for k in ("end_to_end", "per_layer")
               for m in BENCH[k]]
    assert len(set(metrics)) == len(metrics)
    assert len(set(CELLS)) == len(CELLS)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        for cell in m.get("workloads", []):
            assert cell in CELLS
    for m in BENCH["end_to_end"]:
        assert sorted(set(m) - {"workloads"}) == [
            "better", "bound", "name", "source", "unit"]
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert "setup_s" in [m["name"] for m in BENCH["end_to_end"]]
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert sorted(set(m) - {"workloads"}) == [
            "better", "layer", "moves", "name", "source", "unit"]
        assert m["moves"] in e2e
    for w in BENCH["workloads"]:
        assert sorted(w) == ["chips", "config", "name", "traffic", "why"]
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) \
        <= max(1, len(CELLS) // 2)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536


def test_every_entry_resolves_to_files_that_agree_with_it():
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert sorted(c) == ["file", "name", "reduced", "source", "why"]
        assert c["name"] in used
        assert c["file"].startswith("cellbench/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"] and len(c["source"]) <= 200
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert "guarantees" in cfg and "assumed" in cfg
    for cell in CELLS:
        _, entry, config, traffic = run_cell.resolve(cell)
        assert traffic["name"] == entry["traffic"]
        assert traffic["arrival"]["loop"] == "closed"
        assert traffic["arrival"]["clients"] >= 1
        assert abs(sum(o["weight"] for o in traffic["ops"]) - 1) < 1e-9
        for op in traffic["ops"]:
            assert callable(getattr(bytes_fns, op["bytes_fn"]))
    for m in BENCH["per_layer"]:
        with open(os.path.join(HERE, "..", "metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        for k, v in m.items():      # the cells are BENCHMARK.json's alone
            assert k == "workloads" or spec[k] == v, (m["name"], k)
        reader = importlib.import_module(
            "cellbench.readers." + spec["reader"])
        assert callable(reader.read)
    for cell in CELLS:      # every cell reports enough
        assert len(_listed("end_to_end", cell)) >= 2
        assert len(_listed("per_layer", cell)) >= 1


# -- the generator ------------------------------------------------------------

def test_block_counts_are_the_exact_shares():
    assert block_counts([.5, .3, .2], 10) == [5, 3, 2]
    assert block_counts([.475, .285, .19, .05], 200) == [95, 57, 38, 10]


@pytest.mark.parametrize("mix, config_name", [
    ("count-hot24", "baseline-c4"), ("count-hot24-solo", "baseline-c4"),
    ("count-zipf099", "baseline-c4"),
    ("count-zipf099", "baseline-c4-share8"),
    ("rw95-zipf099", "baseline-c4-share8")])
def test_every_seed_sends_the_same_mix_in_another_order(mix, config_name):
    config = _json("configs", config_name + ".json")
    traffic = _json("traffic", mix + ".json")
    block = int(traffic["block"])
    streams = []
    for seed in (3, 2**31 + 11):
        gen = Generator(traffic, config, seed)
        ops = [gen.next() for _ in range(2 * block)]
        again = Generator(traffic, config, seed)
        assert [o.pql for o in ops] == [again.next().pql
                                        for _ in range(2 * block)]
        for o in ops:
            assert len(set(o.rows)) == len(o.rows)
            assert all(0 <= r < config["n_rows"] for r in o.rows)
        streams.append(ops)
    a, b = streams
    assert sorted(o.cls["name"] for o in a[:block]) \
        == sorted(o.cls["name"] for o in b[:block])
    assert [o.pql for o in a] != [o.pql for o in b]


def test_hot_set_walks_its_combinations_before_any_repeat():
    _, _, config, traffic = run_cell.resolve("c4-count-hot")
    gen = Generator(traffic, config, 5)
    pairs = [tuple(sorted(o.rows)) for o in
             (gen.next() for _ in range(500)) if len(o.rows) == 2]
    assert len(pairs) > 200
    assert len(set(pairs[:276])) == len(pairs[:276])
    assert max(max(p) for p in pairs) < 24


# -- the comparison -----------------------------------------------------------

def _Ref() -> Reference:
    """Two rows over 128 columns: row 0 has columns 0..9, row 1 has 5..19."""
    ref = Reference.__new__(Reference)
    ref.rows = np.zeros((2, 2), dtype=np.uint64)
    ref.rows[0, 0] = (1 << 10) - 1
    ref.rows[1, 0] = ((1 << 20) - 1) ^ ((1 << 5) - 1)
    return ref


def _rec(rows, col, sent, done, results, ok=True):
    rec = Record(Op({"name": "x"}, tuple(rows), col, "q"))
    rec.sent, rec.done = sent, done
    rec.status = 200 if ok else 500
    rec.results = results if ok else None
    return rec


@pytest.mark.parametrize("read_at, got, fine", [
    ((2.0, 2.1), 6, True),     # the write was acked at 1.5: it is due
    ((2.0, 2.1), 5, False),    # ... so the old count is stale
    ((1.2, 1.6), 5, True),     # in flight while the read was: either
    ((1.2, 1.6), 6, True),
    ((1.2, 1.6), 7, False),
    ((0.1, 0.2), 5, True),     # read answered before the write was sent
    ((0.1, 0.2), 6, False),
])
def test_a_read_shows_due_writes_and_may_show_racing_ones(read_at, got,
                                                          fine):
    ref = _Ref()
    write = _rec([0], 12, 1.0, 1.5, [True])    # column 12 joins rows 0 & 1
    read = _rec([0, 1], None, *read_at, [got])
    out = check.compare(ref, [write, read], [], {0: 11}, 100, 1)
    assert (out["compared"]["wrong_reads"]["value"] == 0) is fine
    assert out["compared"]["lost_writes"]["value"] == 0


def test_a_lost_write_a_wrong_flag_and_a_failure_are_each_counted():
    ref = _Ref()
    write = _rec([0], 12, 1.0, 1.5, [True])
    out = check.compare(ref, [write], [], {0: 10}, 100, 1)
    assert out["compared"]["lost_writes"]["value"] == 1
    already = _rec([0], 3, 1.0, 1.5, [True])      # bit 3 was set
    out = check.compare(ref, [already], [], {0: 10}, 100, 1)
    assert out["compared"]["wrong_writes"]["value"] == 1
    refused = _rec([0, 1], None, 1.0, 1.5, None, ok=False)
    out = check.compare(ref, [refused], [], {}, 100, 1)
    assert out["compared"]["failed_requests"]["value"] == 1
    # a write sent while warming up is due in the window's reads
    warm = _rec([0], 12, 0.1, 0.2, [True])
    read = _rec([0, 1], None, 1.0, 1.1, [6])
    out = check.compare(ref, [read], [warm], {0: 11}, 100, 1)
    assert out["compared"]["wrong_reads"]["value"] == 0


# -- the trace reduction ------------------------------------------------------

def test_trace_reduction_gives_the_known_split():
    """The trimmed trace's split, worked out straight from the protobuf
    when it was trimmed (PR 24): 117 operations on the device, their
    union 4,753,633,672 ps, first to last event 0.194966675 s."""
    out = trace_reduce.reduce_file(os.path.join(
        HERE, "data", "c4-count-hot.trimmed.xplane.pb"))
    assert out["devices"] == 1
    assert out["busy_s"] == pytest.approx(4.753633672e-3, rel=1e-4)
    assert out["window_s"] == pytest.approx(0.194966675, rel=1e-6)
    assert 1 - out["busy_s"] / out["window_s"] == pytest.approx(
        0.97562, abs=1e-4)
    names = [n for n, _ in out["device_ops"]]
    assert names[0].startswith("%convert_reduce_fusion")
    assert sum(s for _, s in out["device_ops"]) <= out["busy_s"] * 1.0001
    assert len(out["idle_gaps"]) == 10
    assert out["idle_gaps"][0][0] in ("PjitFunction(fn)",
                                      "np.asarray(jax.Array)")
    gaps = [s for _, s in out["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    # a slice the caller clocked is taken as given
    assert trace_reduce.reduce_file(os.path.join(
        HERE, "data", "c4-count-hot.trimmed.xplane.pb"),
        0.5)["window_s"] == 0.5


def test_no_device_plane_reads_as_no_busy_time():
    out = trace_reduce.reduce_events(
        {"/host:CPU": {"python3": [("x", 0.0, 10.0)]}})
    assert out["busy_s"] == 0.0 and out["devices"] == 0


# -- whole runs on the CPU ----------------------------------------------------

@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end_on_the_cpu(cell):
    result = run_cell.run(cell, seed=2**31 + 7, seconds=2.0, trace=False,
                          n_slices=8, allow_cpu=True)
    assert list(result)[:5] == RESULT_KEYS
    assert list(result)[-1] == "compared"
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 20
    assert result["checked"]["reads"] > 20
    assert sorted(result["metrics"]) == sorted(
        m["name"] for m in _listed("end_to_end", cell))
    for m in _listed("end_to_end", cell):
        got = result["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0
    assert result["device"]["platform"] == "cpu"
    assert sorted(result["device"]) == ["count", "kind",
                                        "memory_peak_bytes", "platform"]


def test_the_write_cell_kept_for_later_runs_traced_on_the_cpu():
    bench, entry, config, traffic = _later_rw_cell()
    cell = entry["name"]
    result = run_cell.run_resolved(bench, entry, config, traffic, seed=19,
                                   seconds=4.0, trace=True, n_slices=8,
                                   allow_cpu=True)
    assert result["correct"] is True, result["compared"]
    assert result["checked"]["writes"] > 0
    assert result["checked"]["rows_read_back"] > 0
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert sorted(result["breakdown"]) == ["device_ops", "idle_gaps"]
    listed = {m["name"]: m for m in bench["per_layer"]
              if "workloads" not in m or cell in m["workloads"]}
    assert "wal_wait_ms" in listed
    assert set(result["metrics"]) <= set(listed)
    # what reads the device trace or the residency cache finds nothing
    # to read on a CPU server that answers from the host; the rest is due
    for name, m in listed.items():
        if m["source"] != "device_trace" and name != "residency_hit_pct":
            assert result["metrics"][name]["unit"] == m["unit"], name


def test_the_command_fails_without_a_tpu(tmp_path):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "cellbench", "run_cell.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env=dict(os.environ, TMPDIR=str(tmp_path)))
    assert out.returncode != 0
    assert out.stdout == ""
    assert "not 'tpu'" in out.stderr


@pytest.mark.parametrize("fault, write, number", [
    ("answer_plus_one", False, "wrong_reads"),   # the planted fault
    ("drop_slice", False, "wrong_reads"),        # control: approximate
    ("lose_write", True, "lost_writes"),         # control: ack, not applied
])
def test_a_broken_guarantee_comes_out_as_not_correct(fault, write, number):
    """The rest of a run, the chip look skipped, with the timed path
    broken underneath the server's executor."""
    parts = _later_rw_cell() if write else run_cell.resolve(CELLS[0])
    result = run_cell.run_resolved(*parts, seed=23, seconds=2.0,
                                   trace=False, n_slices=4, allow_cpu=True,
                                   child=FAULTY + [fault])
    assert result["correct"] is False
    assert result["compared"][number]["value"] > 0
