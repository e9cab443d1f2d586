"""The cell ``c4-count-hot-mesh4`` (PR 27): its four readers on hand-made
``Run``s, its files agreeing with ``BENCHMARK.json``, and a rehearsal of
the whole cell on the CPU with a child that has four (virtual) devices.
Run with ``pytest cellbench/tests``."""

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
from cellbench.lib import bytes_fns, trace_reduce             # noqa: E402
from cellbench.lib.loadgen import Record                      # noqa: E402
from cellbench.lib.traffic import Op                          # noqa: E402

CELL = "c4-count-hot-mesh4"
METRICS = ["mesh_served_pct", "mesh_roofline", "collective_pct",
           "mesh_balance_pct"]
PEAK = {"hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
_, ENTRY, CONFIG, TRAFFIC = run_cell.resolve(CELL)
ONE_CHIP = run_cell.resolve("c4-count-hot")[2]


def _spec(metric: str) -> dict:
    with open(os.path.join(ROOT, "cellbench", "metrics",
                           metric + ".json")) as f:
        return json.load(f)


def _read(metric: str):
    return importlib.import_module(
        "cellbench.readers." + _spec(metric)["reader"]).read


def _rec(k: int, done: float, stats: dict, ok: bool = True) -> Record:
    cls = {"name": f"count_intersect{k}", "bytes_fn": "dense_leaves"}
    rec = Record(Op(cls, tuple(range(k)), None, "q"))
    rec.sent, rec.done, rec.latency_s = done - 0.01, done, 0.01
    rec.status = 200 if ok else 500
    rec.results = [1] if ok else None
    rec.stats = stats
    return rec


def _run(records, config=CONFIG, vars_after=None, trace=None):
    run = run_cell.Run()
    run.config, run.traffic, run.peak = config, TRAFFIC, PEAK
    run.records = records
    if vars_after is not None:
        run.before = {"status": {}, "vars": {}}
        run.after = {"status": {}, "vars": vars_after}
    run.trace = trace
    return run


def _served(width: int | None) -> dict:
    stats = {"devicePrograms": 0 if width is None else 1}
    if width:
        stats["meshDevices"] = width
    return stats


# -- the files ----------------------------------------------------------------

def test_the_cell_and_its_configuration_are_what_the_issue_names():
    assert ENTRY == {
        "name": CELL, "config": "baseline-c4-mesh4",
        "traffic": "count-hot24", "chips": 4, "why": ENTRY["why"]}
    assert BENCH["workloads"][-1] == ENTRY      # appended, not inserted
    assert BENCH["configs"][-1]["name"] == "baseline-c4-mesh4"
    assert BENCH["configs"][-1]["reduced"] == ["mesh_devices"]
    assert CONFIG["mesh_devices"] == 4
    assert CONFIG["reduced"] == {"mesh_devices": [8, 4]}
    # the same data, letter for letter, as the one-chip configuration
    for key in ("index", "frame", "n_slices", "n_rows", "d0", "zipf_s",
                "run_rows", "bsi", "guarantees"):
        assert CONFIG[key] == ONE_CHIP[key], key
    for key, said in ONE_CHIP["assumed"].items():
        assert CONFIG["assumed"][key] == said
    assert "mesh_devices" not in ONE_CHIP


@pytest.mark.parametrize("metric", METRICS)
def test_metric_file_agrees_with_benchmark_json(metric):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    spec = _spec(metric)
    for k, v in entry.items():
        assert k == "workloads" or spec[k] == v, k
    assert entry["workloads"] == [CELL]
    assert [m["name"] for m in BENCH["per_layer"][-4:]] == METRICS
    assert spec["what"] and callable(_read(metric))


def test_no_accepted_roofline_share_lists_the_mesh_cell():
    for m in BENCH["per_layer"]:
        if m["name"] == "kernels_roofline":
            assert m["workloads"] == ["c4-count-hot",
                                      "c4-count-hot-solo"]


# -- mesh_served_pct ----------------------------------------------------------

@pytest.mark.parametrize("widths, want", [
    ([4, 4, 4, 4], 100.0),
    ([4, 4, None, 1], 50.0),    # a host-served and a one-device answer
    ([1, 1, 1, 1], 0.0),        # a server that meshed one device of four
], ids=["all", "half", "narrow"])
def test_mesh_served_counts_reads_on_the_stated_width(widths, want):
    run = _run([_rec(2, 1.0 + i, _served(w))
                for i, w in enumerate(widths)]
               + [_rec(2, 9.0, _served(4), ok=False)])   # not answered
    assert _read("mesh_served_pct")(run) == pytest.approx(want)


def test_mesh_served_is_silent_on_a_program_without_the_field():
    read = _read("mesh_served_pct")
    parent = [_rec(2, 1.0, {"devicePrograms": 1}) for _ in range(3)]
    assert read(_run(parent)) is None
    assert read(_run(parent, vars_after={"deviceBlockCache": {}})) is None
    # the field exists (/debug/vars has ``mesh``) and no read carries
    # it: every read came from the host, which is 0 and not silence
    assert read(_run(parent, vars_after={"mesh": None})) == 0.0
    assert read(_run([], vars_after={"mesh": None})) is None
    assert read(_run([_rec(2, 1.0, _served(4))], config=ONE_CHIP)) is None


# -- the two shares of the device trace ---------------------------------------

# Names as the four-chip trace has them (seed 2700000002, PR 27;
# ``readers/collective.py`` describes it).
FUSION = ("%convert_reduce_fusion = s32[64]{0:T(128)S(1)} fusion(u32[64,"
          "32768]{1,0:T(8,128)} %param, u32[64,32768]{1,0:T(8,128)}"
          " %param.1), kind=kLoop, calls=%fused_computation")
ALL_REDUCE = ("%all-reduce.2 = (s32[1,1]{0,1:T(1,128)}, s32[1,1]{0,1:"
              "T(1,128)}) all-reduce(s32[1,1]{0,1:T(1,128)} %bitcast.1,"
              " s32[1,1]{0,1:T(1,128)} %bitcast), channel_id=2,"
              " replica_groups=[1,4]<=[4], use_global_device_ids=true,"
              " to_apply=%region_2.0.clone")
AFTER = ("%pad_add_fusion = s32[2,1]{0,1:T(1,128)} fusion(s32[1,1]{0,1:"
         "T(1,128)} %get-tuple-element.2, s32[1,1]{0,1:T(1,128)}"
         " %get-tuple-element.3), kind=kLoop, calls=%fused_computation.1")


def _trace(n_planes: int, fusion_ns: float, reduce_ns: float,
           n_programs: int, window_s: float = 3.0) -> dict:
    """``n_programs`` programs a device plane, back to back with gaps:
    the fusion, the reduction, and an operation that only names it."""
    planes = {}
    for d in range(n_planes):
        ops = []
        for i in range(n_programs):
            t = 1e6 + i * 4 * (fusion_ns + reduce_ns)
            ops.append((FUSION, t, t + fusion_ns))
            ops.append((ALL_REDUCE, t + fusion_ns,
                        t + fusion_ns + reduce_ns))
            ops.append((AFTER, t + fusion_ns + reduce_ns,
                        t + fusion_ns + reduce_ns))
        planes[f"/device:TPU:{d}"] = {"XLA Ops": ops, "XLA Modules": []}
    planes["/host:CPU"] = {"python3": [("pilosa.legs_wait", 0.0, 1e9)]}
    out = trace_reduce.reduce_events(planes, window_s)
    out.update(t0=100.0, t1=100.0 + window_s)
    return out


def _window_reads(n: int, k: int = 2) -> list:
    return [_rec(k, 100.5 + i * 1e-3, _served(4)) for i in range(n)]


def test_four_planes_at_the_aggregate_roofline_read_100():
    n, k = 50, 2
    need = bytes_fns.dense_leaves(_window_reads(1, k)[0].op, CONFIG)
    assert need == k * 256 * 131072
    per_device_ns = need / 4 / PEAK["hbm_bytes_per_s"] * 1e9
    tr = _trace(4, per_device_ns, 0.0, n)
    assert tr["devices"] == 4
    run = _run(_window_reads(n, k), trace=tr)
    assert _read("mesh_roofline")(run) == pytest.approx(100.0, rel=1e-6)
    # twice the time a program: half the share; never above 100
    slow = _run(_window_reads(n, k),
                trace=_trace(4, per_device_ns, per_device_ns, n))
    assert _read("mesh_roofline")(slow) == pytest.approx(50.0, rel=1e-6)
    assert _read("collective_pct")(slow) == pytest.approx(50.0, rel=1e-6)
    assert _read("collective_pct")(run) == pytest.approx(0.0, abs=1e-9)


def test_one_plane_on_a_four_device_configuration_reads_25_at_most():
    """A program that read every byte on ONE chip at that chip's peak:
    the most a one-device server can show against four chips' roofline."""
    n, k = 40, 3
    need = bytes_fns.dense_leaves(_window_reads(1, k)[0].op, CONFIG)
    tr = _trace(1, need / PEAK["hbm_bytes_per_s"] * 1e9, 0.0, n)
    assert tr["devices"] == 1
    run = _run(_window_reads(n, k), trace=tr)
    assert _read("mesh_roofline")(run) == pytest.approx(25.0, rel=1e-6)


def test_reads_outside_the_slice_or_from_the_host_need_no_bytes():
    tr = _trace(4, 1e5, 1e4, 10)
    outside = [_rec(2, 99.0, _served(4)), _rec(2, 104.0, _served(4))]
    host = [_rec(2, 100.5, _served(None))]
    assert _read("mesh_roofline")(_run(outside + host, trace=tr)) is None
    assert _read("mesh_roofline")(
        _run(_window_reads(3), config=ONE_CHIP, trace=tr)) is None


def test_the_trace_shares_are_silent_without_a_device_trace():
    for metric in ("mesh_roofline", "collective_pct"):
        read = _read(metric)
        assert read(_run(_window_reads(3))) is None
        idle = trace_reduce.reduce_events(
            {"/host:CPU": {"python3": [("x", 0.0, 10.0)]}}, 3.0)
        idle.update(t0=100.0, t1=103.0)
        assert read(_run(_window_reads(3), trace=idle)) is None


def test_collective_is_silent_where_no_operation_is_the_reduction():
    """One chip: the program has no all-reduce (an operation that only
    takes the reduction's result as its operands does not count as
    one)."""
    planes = {"/device:TPU:0": {"XLA Ops": [
        (FUSION, 0.0, 5e4), (AFTER, 5e4, 6e4)]}}
    tr = trace_reduce.reduce_events(planes, 3.0)
    tr.update(t0=100.0, t1=103.0)
    assert _read("collective_pct")(_run(_window_reads(1),
                                        trace=tr)) is None


def test_collective_share_never_passes_the_busy_time():
    tr = _trace(4, 0.0, 2e4, 30)        # nothing but the reduction
    got = _read("collective_pct")(_run(_window_reads(30), trace=tr))
    assert got == pytest.approx(100.0, rel=1e-6)


# -- mesh_balance_pct ---------------------------------------------------------

def _cache(per_device: dict | None) -> dict:
    cache = {"entries": 24, "usedBytes": 805306368, "hits": 1, "misses": 1}
    if per_device is not None:
        cache["perDeviceBytes"] = per_device
    return {"deviceBlockCache": cache}


@pytest.mark.parametrize("per_device, want", [
    ({"0": 201326592, "1": 201326592, "2": 201326592, "3": 201326592},
     100.0),
    ({"0": 805306368}, 0.0),                 # every slab whole on one
    ({"0": 300, "1": 300, "2": 300}, 0.0),   # a chip that holds nothing
    ({"0": 400, "1": 300, "2": 200, "3": 400}, 50.0),
], ids=["even", "one-chip", "three", "uneven"])
def test_mesh_balance_is_least_over_most(per_device, want):
    run = _run([], vars_after=_cache(per_device))
    assert _read("mesh_balance_pct")(run) == pytest.approx(want)


def test_mesh_balance_is_silent_where_there_is_nothing_to_read():
    read = _read("mesh_balance_pct")
    assert read(_run([])) is None                        # untraced
    assert read(_run([], vars_after={})) is None
    assert read(_run([], vars_after=_cache(None))) is None
    assert read(_run([], vars_after=_cache({}))) is None
    even = _cache({"0": 1, "1": 1, "2": 1, "3": 1})
    assert read(_run([], config=ONE_CHIP, vars_after=even)) is None


# -- the whole cell on the CPU ------------------------------------------------

def test_the_cell_runs_traced_on_a_four_device_cpu_child(monkeypatch):
    """8 slices, a child with four virtual CPU devices: the server
    forms mesh (1, 4) by itself, every read it serves by a device
    program says ``meshDevices`` 4, and the slabs lie evenly. A CPU
    server routes some reads to the host, so the share served on the
    mesh equals the share served by a device program, whatever it is."""
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=4")
    result = run_cell.run(CELL, seed=2**31 + 27, seconds=3.0, trace=True,
                          n_slices=8, allow_cpu=True)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 20
    assert result["device"]["count"] == 4
    got = {k: v["value"] for k, v in result["metrics"].items()}
    listed = {m["name"] for m in BENCH["per_layer"]
              if "workloads" not in m or CELL in m["workloads"]}
    assert set(got) <= listed
    assert "kernels_roofline" not in got
    assert got["mesh_served_pct"] == pytest.approx(
        got["device_served_pct"])
    if got["device_served_pct"] > 0:
        assert got["mesh_balance_pct"] == pytest.approx(100.0)
    # a CPU child has no /device:TPU plane: nothing to read
    assert "mesh_roofline" not in got and "collective_pct" not in got
