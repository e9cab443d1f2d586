"""The ten stage metrics (PR 25): each reader on a hand-made ``Run``
(its value, None where ``queryStages`` is absent, None where the lane's
``requests`` grew by another count than the answered reads), and each
metric's file agreeing with its ``BENCHMARK.json`` entry."""

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
from cellbench.lib.loadgen import Record                      # noqa: E402
from cellbench.lib.traffic import Op                          # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)

N = 4                   # answered reads of the hand-made window
LATENCY_S = 0.012       # each


def _acct(n, wall_us, cpu_us=None):
    """A stage's counters; a background loop's carry its CPU too."""
    out = {"n": n, "wallUs": wall_us}
    if cpu_us is not None:
        out["cpuUs"] = cpu_us
    return out


# What the window added, per stage, over N reads: the connection
# thread's stages tile 10 ms a read; the leg's run inside legs_wait.
STAGES = {
    "http_read": _acct(N, 400), "parse": _acct(N, 800),
    "setup": _acct(N, 1200), "admission": _acct(N, 40),
    "execute": _acct(N, 2000), "plan": _acct(N, 1600),
    "route": _acct(N, 400), "legs_wait": _acct(N, 28000),
    "merge": _acct(N, 160), "finish": _acct(N, 4000),
    "encode": _acct(N, 600), "http_write": _acct(N, 800)}
OFF = {
    "leg": _acct(N, 2000), "route": _acct(2 * N, 3600),
    "dispatch": _acct(2 * N, 4000),
    "compile": _acct(1, 2000), "fetch": _acct(N, 8000),
    "merge": _acct(N, 400)}
CPU_US, OFF_CPU_US = 11600, 11600     # the two threads' CPU in the window
TICKS = {"runtime": _acct(5, 100000, 60000),
         "wal_flush": _acct(50, 40000, 40000)}

EXPECTED = {
    "http_ms": (400 + 600 + 800) / N / 1e3,
    "bookkeeping_ms": (1200 + 4000) / N / 1e3,
    "parse_plan_ms": (800 + 1600) / N / 1e3,
    "admission_wait_ms": 40 / N / 1e3,
    "route_ms": (2000 + 2000 + 400 + 3600 + 160 + 400) / N / 1e3,
    "dispatch_ms": (4000 + 2000) / N / 1e3,
    "fetch_ms": 8000 / N / 1e3,
    # wall 40,000 us on the connection thread; CPU 11,600 there and
    # 11,600 on the leg's thread.
    "host_blocked_pct": 100.0 * (40000 - CPU_US - OFF_CPU_US) / 40000,
    "background_cpu_pct": 100.0 * 0.1 / 50.0,
    "unattributed_ms": LATENCY_S * 1e3 - 40000 / N / 1e3,
}


def _grown(base: dict, by: dict) -> dict:
    out = {k: dict(v) for k, v in base.items()}
    for name, d in by.items():
        b = out.get(name) or {k: 0 for k in d}
        out[name] = {k: b[k] + d[k] for k in d}
    return out


def _surfaces(requests, cpu, stages, off, ticks, at) -> dict:
    return {"status": {}, "vars": {
        "queryStages": {"read": {"requests": requests, "cpuUs": cpu[0],
                                 "offThreadCpuUs": cpu[1],
                                 "stages": stages, "offThread": off},
                        "write": {"requests": 7, "cpuUs": 5,
                                  "offThreadCpuUs": 0, "stages": {},
                                  "offThread": {}}},
        "backgroundTicks": ticks, "sampledAt": at, "compileLog": []}}


def _run(grown_by: int = N, with_stages: bool = True):
    """A Run whose window answered N reads (and one failed)."""
    run = run_cell.Run()
    cls = {"name": "count2", "weight": 1.0, "bytes_fn": "count_intersect"}
    for i in range(N + 1):
        rec = Record(Op(cls, (1, 2), None, "Count(...)"))
        rec.latency_s = LATENCY_S
        rec.status = 200 if i < N else 500
        rec.results = [1] if i < N else None
        run.records.append(rec)
    warm = {"parse": _acct(400, 90000), "fetch": _acct(10, 999)}
    warm_off = {"fetch": _acct(400, 700000)}
    warm_ticks = {"runtime": _acct(12, 250000, 150000)}
    run.before = _surfaces(400, (900000, 500000), warm, warm_off,
                           warm_ticks, 1000.0)
    run.after = _surfaces(400 + grown_by,
                          (900000 + CPU_US, 500000 + OFF_CPU_US),
                          _grown(warm, STAGES),
                          _grown(warm_off, OFF),
                          _grown(warm_ticks, TICKS), 1050.0)
    if not with_stages:
        for s in (run.before, run.after):
            for key in ("queryStages", "backgroundTicks", "sampledAt"):
                del s["vars"][key]
    return run


def _reader(metric: str):
    with open(os.path.join(ROOT, "cellbench", "metrics",
                           metric + ".json")) as f:
        spec = json.load(f)
    return importlib.import_module(
        "cellbench.readers." + spec["reader"]).read


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_reads_the_windows_delta(metric):
    assert _reader(metric)(_run()) == pytest.approx(EXPECTED[metric])


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_is_silent_without_the_stage_clock(metric):
    """The parent commit's /debug/vars has no ``queryStages``: the
    reader returns nothing and does not raise."""
    assert _reader(metric)(_run(with_stages=False)) is None
    untraced = _run()
    untraced.before = untraced.after = None
    assert _reader(metric)(untraced) is None


@pytest.mark.parametrize("metric", sorted(
    set(EXPECTED) - {"background_cpu_pct"}))
def test_reader_is_silent_when_the_counts_disagree(metric):
    """Requests folded in between the two reads that the window did
    not answer (or the other way round): the delta is not the window."""
    assert _reader(metric)(_run(grown_by=N + 1)) is None
    assert _reader(metric)(_run(grown_by=N - 1)) is None


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_metric_file_agrees_with_benchmark_json(metric):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    with open(os.path.join(ROOT, "cellbench", "metrics",
                           metric + ".json")) as f:
        spec = json.load(f)
    for k, v in entry.items():
        assert k == "workloads" or spec[k] == v, (metric, k)
    assert entry["workloads"] == ["c4-count-hot", "c4-count-hot-solo"]
    assert entry["better"] == "lower"
    assert entry["source"] == ("host_clock" if metric == "unattributed_ms"
                               else "program_counter")
    assert spec["what"] and callable(_reader(metric))


def test_the_ten_are_appended_after_the_six():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[:6] == ["device_served_pct", "residency_hit_pct",
                         "compiles_in_window", "kernels_roofline",
                         "device_idle_pct", "import_mbit_s"]
    assert sorted(names[6:]) == sorted(EXPECTED)
