"""The deployment ``baseline-c4-share8-ycsb-b``: YCSB workload B (95 %
reads, 5 % updates, Zipf keys) on one chip's share of BASELINE config 4,
and what it forced — a write retires only its own row's resident slab.

The benchmark's cell ``share8-rw95`` holds it on the chip at 32 slices x
512 rows against the program's 1 GiB; here the configuration's own
generator makes 4 slices x 64 rows and the budget is half the slabs.
What is tested beside the rehearsal: a write drops its row's slab and no
other; the next read after the ack sees the bit; a fill that raced a
write keeps nothing; an import still retires the whole view and a
snapshot retires nothing; the counters the cell's two metrics read, and
the readers on hand-made surfaces.
"""

import http.client
import json
import os
import threading
import time

import numpy as np
import pytest

from cellbench import run_cell
from cellbench.lib import loadgen
from cellbench.lib import server as bench_server
from cellbench.lib.data import SLICE_WIDTH, Reference
from cellbench.lib.traffic import Op
from pilosa_tpu.executor import ExecOptions, Executor
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.models.view import VIEW_STANDARD
from pilosa_tpu.obs import accounting
from pilosa_tpu.ops import packed
from pilosa_tpu.parallel import mesh as mesh_mod
from pilosa_tpu.parallel import residency
from pilosa_tpu.sched import QueryContext
from pilosa_tpu.server.server import Server

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "share8-rw95"
CONFIG_NAME = "baseline-c4-share8-ycsb-b"
BENCH, ENTRY, CONFIG, TRAFFIC = run_cell.resolve(CELL)
SLAB_ROW = packed.WORDS_PER_SLICE * 4      # one slice of one row, dense
CHILD = [os.path.join(ROOT, "tests", "rw95_child.py")]
JOIN_S = 60.0

NEW_METRICS = {
    # name: (reader module, unit, better, moves)
    "write_retired_slabs": ("write_retired", "slabs", "lower", "qps"),
    "write_refill_pct": ("write_refill", "%", "lower", "read_p95_ms"),
}
GENERATOR_KEYS = ("index", "frame", "n_slices", "n_rows", "d0", "zipf_s",
                  "run_rows", "bsi")


def _small(n_slices: int = 4, n_rows: int = 64) -> dict:
    return dict(CONFIG, n_slices=n_slices, n_rows=n_rows)


# -- (a) the files -------------------------------------------------------------

def test_the_configuration_is_share8_with_ycsb_b_updates():
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG_NAME)
    assert entry["file"] == f"cellbench/configs/{CONFIG_NAME}.json"
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert "YCSB core workload B" in entry["source"]
    assert entry["reduced"] == list(CONFIG["reduced"]) == ["n_slices"]
    assert CONFIG["reduced"]["n_slices"] == [256, 32]
    assert 0 < len(entry["why"]) <= 200
    # the read-only share's generator, letter for letter
    share8 = run_cell.resolve("share8-count-zipf")[2]
    for key in GENERATOR_KEYS:
        assert CONFIG[key] == share8[key], key
    assert CONFIG["bsi"] is None
    for key in ("deployment", "assumed", "guarantees", "sizes"):
        assert CONFIG[key], key
    for key in ("answers", "writes", "read_back", "changed_flag",
                "wal_fsync"):
        assert CONFIG["guarantees"][key], key
    assert CONFIG["guarantees"]["wal_fsync"].startswith("none")


def test_the_same_seed_gives_the_same_bits_as_the_read_only_share():
    share8 = run_cell.resolve("share8-count-zipf")[2]
    a = Reference(39, dict(CONFIG, n_slices=2, n_rows=16))
    b = Reference(39, dict(share8, n_slices=2, n_rows=16))
    assert all(np.array_equal(x, y) for x, y in zip(a.rows, b.rows))


def test_the_cell_sends_ycsb_b_to_the_share():
    assert {k: ENTRY[k] for k in ("name", "config", "traffic", "chips")} \
        == {"name": CELL, "config": CONFIG_NAME,
            "traffic": "rw95-zipf099", "chips": 1}
    assert 0 < len(ENTRY["why"]) <= 200
    assert TRAFFIC["arrival"] == {"loop": "closed", "clients": 8}
    assert TRAFFIC["block"] == 200
    assert [(o["template"], o.get("leaves"), o["weight"])
            for o in TRAFFIC["ops"]] == [
        ("count_intersect", 2, 0.475), ("count_intersect", 3, 0.285),
        ("count_intersect", 4, 0.19), ("setbit", None, 0.05)]
    assert TRAFFIC["keys"] == {"law": "zipf", "s": 0.99}
    assert (TRAFFIC["warm_requests"], TRAFFIC["check_sample"]) == (400, 4096)
    # a (configuration, traffic) pair appears once
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_metric_file_agrees_with_benchmark_json(name):
    reader, unit, better, moves = NEW_METRICS[name]
    with open(os.path.join(ROOT, "cellbench", "metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    for k, v in entry.items():
        assert k == "workloads" or spec[k] == v, k
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": "program_counter", "layer": "residency",
                     "moves": moves, "workloads": [CELL]}
    assert spec["reader"] == reader and spec["what"]
    assert callable(run_cell._reader(name))
    e2e = next(m for m in BENCH["end_to_end"] if m["name"] == moves)
    assert "workloads" not in e2e


def test_the_cell_reports_the_unlisted_metrics_and_its_own_two():
    listed = {m["name"] for m in run_cell._listed(BENCH["per_layer"], CELL)}
    assert listed == set(NEW_METRICS) | {
        "device_served_pct", "compiles_in_window", "device_idle_pct",
        "import_mbit_s"}
    assert {m["name"] for m in run_cell._listed(BENCH["end_to_end"],
                                                CELL)} \
        == {"read_p50_ms", "read_p95_ms", "qps", "setup_s"}


# -- (b) the rehearsal: the cell through the harness, on the CPU ---------------

def _rehearse(seed: int, child: list, trace: bool) -> dict:
    """The cell's own run at 4 slices x 64 rows, a 3 s window, through a
    server child that takes the device path on the CPU with a budget of
    half the 64 slabs."""
    return run_cell.run_resolved(BENCH, ENTRY, _small(), TRAFFIC, seed,
                                 3.0, trace, n_slices=4, allow_cpu=True,
                                 child=child)


def test_the_rehearsal_reads_correct_with_writes_checked():
    result = _rehearse(2**31 + 39, CHILD, trace=True)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0
    assert all(c["value"] == 0 for c in result["compared"].values())
    assert result["checked"]["writes"] > 20
    assert result["checked"]["rows_read_back"] > 5
    metrics = result["metrics"]
    assert metrics["device_served_pct"]["value"] == 100.0
    # a write retires at most its own row's slab: one slice set here
    assert 0 < metrics["write_retired_slabs"]["value"] <= 1.0
    assert 0 < metrics["write_refill_pct"]["value"] < 100.0


def test_with_retirement_switched_off_the_comparison_reads_wrong():
    """The control of a retirement that is too narrow: a write that moves
    no row's token and drops no slab leaves stale slabs that later reads
    are served from, and the comparison catches them."""
    result = _rehearse(2**31 + 40, CHILD + ["--no-retire"], trace=False)
    assert result["correct"] is False
    assert result["compared"]["wrong_reads"]["value"] > 0
    assert result["checked"]["writes"] > 20


# -- (c) the system against the reference under writes -------------------------

@pytest.fixture
def cache(monkeypatch):
    """A residency cache of this test's own (the process-wide one
    carries other tests' slabs and counters)."""
    def install(budget_bytes: int):
        c = residency.DeviceBlockCache(budget_bytes)
        monkeypatch.setattr(residency, "_device_cache", c)
        return c
    return install


@pytest.fixture
def loaded(tmp_path, monkeypatch, cache):
    """The configuration's generator at 4 slices x 16 rows in a holder,
    an executor that meshes one device, a budget for every slab."""
    monkeypatch.setenv("PILOSA_TPU_SPARSE_UPLOAD", "interpret")
    config = _small(4, 16)
    c = cache(1 << 30)
    ref = Reference(39, config)
    holder = Holder(str(tmp_path / "h"))
    holder.open()
    rows, cols = ref.slice_positions(0, 4)
    holder.create_index("i").create_frame("f").import_bits(rows, cols)
    ex = Executor(holder, host="local", use_mesh=True, mesh_min_slices=1)
    ex._mesh = mesh_mod.make_mesh(1)
    try:
        yield ex, holder, c, ref
    finally:
        ex.close()
        holder.close()


def _count(ex, rows) -> tuple[int, dict]:
    ctx = QueryContext()
    accounting.attach(ctx)
    pql = "Count(Intersect(%s))" % ", ".join(
        f"Bitmap(frame=f, rowID={r})" for r in rows)
    got = ex.execute("i", pql, None, ExecOptions(ctx=ctx))[0]
    return got, ctx.cost.summary()


def _setbit(ex, row: int, col: int) -> tuple[bool, dict]:
    ctx = QueryContext()
    accounting.attach(ctx)
    got = ex.execute("i", f"SetBit(frame=f, rowID={row}, columnID={col})",
                     None, ExecOptions(ctx=ctx))[0]
    return got, ctx.cost.summary()


def _new_col(ref: Reference, rows) -> int:
    """A column set in every row of ``rows`` but the first, unset there:
    a SetBit of the first row there adds one to their intersection."""
    first, rest = rows[0], rows[1:]
    for col in range(3 * SLICE_WIDTH, 4 * SLICE_WIDTH):
        if not ref.bit(first, col) and all(ref.bit(r, col) for r in rest):
            return col
    raise AssertionError("fixture: no such column")


def test_a_write_retires_its_own_rows_slab_and_no_other(loaded):
    ex, holder, c, ref = loaded
    pairs = [(0, 1), (2, 3), (1, 2)]
    for rows in pairs:
        assert _count(ex, rows)[0] == ref.count_intersect(rows)
    assert c.fills == 4 and c.snapshot()["entries"] == 4
    col = _new_col(ref, (2, 1))
    changed, stats = _setbit(ex, 2, col)
    assert changed is True
    # one resident slab, row 2's, left the cache; the ack says so
    assert stats["retiredSlabs"] == 1
    snap = c.snapshot()
    assert snap["entries"] == 3 and snap["usedBytes"] == 3 * 4 * SLAB_ROW
    assert snap["retired"] == {"byWrite": 1, "byWriteBytes": 4 * SLAB_ROW,
                               "all": 0}
    # the other rows still hit: no fill, no mark of a cold read
    hits = c.hits
    got, stats = _count(ex, (0, 1))
    assert got == ref.count_intersect((0, 1))
    assert "coldLeaves" not in stats and "fillWaits" not in stats
    assert c.hits == hits + 2 and c.fills == 4
    # the next read of the written row sees the bit, and refills it
    got, stats = _count(ex, (1, 2))
    assert got == ref.count_intersect((1, 2)) + 1
    assert stats["coldLeaves"] == 1
    assert (c.fills, c.refills) == (5, 1)
    # a SetBit that changes nothing retires nothing
    changed, stats = _setbit(ex, 2, col)
    assert changed is False and "retiredSlabs" not in stats
    assert c.retired_by_write == 1


def test_a_write_to_a_row_no_slab_holds_retires_nothing(loaded):
    ex, holder, c, ref = loaded
    assert _count(ex, (0, 1))[0] == ref.count_intersect((0, 1))
    col = next(x for x in range(SLICE_WIDTH) if not ref.bit(9, x))
    changed, stats = _setbit(ex, 9, col)
    assert changed is True and "retiredSlabs" not in stats
    assert c.snapshot()["entries"] == 2 and c.retired_by_write == 0
    assert _count(ex, (0, 9))[0] == ref.count_intersect((0, 9)) + (
        1 if ref.bit(0, col) else 0)
    assert c.refills == 0


def test_a_fill_racing_a_write_keeps_nothing(loaded, monkeypatch):
    """A read takes row 3's key, then a write to row 3 lands and is
    acknowledged while the read's fill is packing: the fill hands its
    array to its own read and keeps nothing — no slab a later read
    could find, no budget held until LRU reaches it."""
    ex, holder, c, ref = loaded
    packing, release = threading.Event(), threading.Event()
    real = residency._fill

    def slow_fill(mesh, lead_shape, collect, **tags):
        if tags.get("row") == 3 and not release.is_set():
            packing.set()
            assert release.wait(JOIN_S)
        return real(mesh, lead_shape, collect, **tags)

    monkeypatch.setattr(residency, "_fill", slow_fill)
    out = {}
    reader = threading.Thread(
        target=lambda: out.setdefault("got", _count(ex, (0, 3))[0]))
    reader.start()
    assert packing.wait(JOIN_S)
    mesh = ex._mesh_or_none()
    leaf = ("f", VIEW_STANDARD, 3)
    raced = ex._leaf_cache_key(mesh, "i", leaf, list(range(4)))
    col = _new_col(ref, (3, 0))
    assert _setbit(ex, 3, col)[0] is True            # acknowledged
    release.set()
    reader.join(JOIN_S)
    assert not reader.is_alive()
    # the racing read may count the write or not: it was in flight
    assert out["got"] in (ref.count_intersect((0, 3)),
                          ref.count_intersect((0, 3)) + 1)
    now = ex._leaf_cache_key(mesh, "i", leaf, list(range(4)))
    assert raced != now
    assert not c.contains(raced) and not c.contains(now)
    snap = c.snapshot()
    assert snap["entries"] == 1 and snap["usedBytes"] == 4 * SLAB_ROW
    # the read sent after the ack builds anew and sees the bit
    got, stats = _count(ex, (0, 3))
    assert got == ref.count_intersect((0, 3)) + 1
    assert stats["coldLeaves"] == 1 and c.contains(now)


def test_an_import_retires_the_whole_view_and_a_snapshot_none(loaded):
    ex, holder, c, ref = loaded
    for rows in ((0, 1), (2, 3)):
        _count(ex, rows)
    assert c.snapshot()["entries"] == 4
    # a snapshot swaps the storage and changes no bit: every slab stays
    # and keeps being hit
    holder.fragment("i", "f", VIEW_STANDARD, 2).snapshot()
    hits = c.hits
    assert _count(ex, (0, 1))[0] == ref.count_intersect((0, 1))
    assert c.hits == hits + 2 and c.fills == 4
    assert c.snapshot()["retired"] == {"byWrite": 0, "byWriteBytes": 0,
                                       "all": 0}
    # a bulk import may change any row: the view's slabs all go at once
    # (a few bits go through the write lane: their rows alone)
    cols = np.arange(3 * SLICE_WIDTH, 3 * SLICE_WIDTH + 8192,
                     dtype=np.uint64)
    holder.frame("i", "f").import_bits(np.full(len(cols), 7, np.uint64),
                                       cols)
    snap = c.snapshot()
    assert snap["entries"] == 0 and snap["usedBytes"] == 0
    assert snap["retired"] == {"byWrite": 0, "byWriteBytes": 0, "all": 4}
    assert _count(ex, (0, 1))[1]["coldLeaves"] == 2
    gain = sum(1 for x in cols.tolist()
               if ref.bit(0, x) and not ref.bit(7, x))
    assert gain > 0
    assert _count(ex, (0, 7))[0] == ref.count_intersect((0, 7)) + gain


def test_a_reopened_fragment_retires_the_view(loaded):
    ex, holder, c, ref = loaded
    _count(ex, (0, 1))
    frag = holder.fragment("i", "f", VIEW_STANDARD, 1)
    frag.close()
    frag.open()
    assert c.snapshot()["entries"] == 0
    assert c.snapshot()["retired"]["all"] == 2
    assert _count(ex, (0, 1))[0] == ref.count_intersect((0, 1))


def test_an_evicted_slab_leaves_no_trace_to_retire(tmp_path, monkeypatch,
                                                   cache):
    """LRU takes a row's slab out before any write: the index of the
    rows' slabs forgets it, and the write retires nothing."""
    monkeypatch.setenv("PILOSA_TPU_SPARSE_UPLOAD", "interpret")
    ref = Reference(39, _small(4, 16))
    c = cache(2 * 4 * SLAB_ROW)                 # two slabs
    holder = Holder(str(tmp_path / "h"))
    holder.open()
    rows, cols = ref.slice_positions(0, 4)
    holder.create_index("i").create_frame("f").import_bits(rows, cols)
    ex = Executor(holder, host="local", use_mesh=True, mesh_min_slices=1)
    ex._mesh = mesh_mod.make_mesh(1)
    try:
        _count(ex, (0, 1))
        _count(ex, (2, 3))                      # evicts rows 0 and 1
        assert c.evictions == 2
        col = next(x for x in range(SLICE_WIDTH) if not ref.bit(0, x))
        changed, stats = _setbit(ex, 0, col)
        assert changed is True and "retiredSlabs" not in stats
        assert c.snapshot()["entries"] == 2 and c.retired_by_write == 0
        view = holder.view("i", "f", VIEW_STANDARD)
        assert set(c._owned[view.uid]) == {2, 3}
    finally:
        ex.close()
        holder.close()


# -- (d) the write's answer and its span over HTTP -----------------------------

class _Alive:
    """What ``cellbench.lib.server.Http`` asks of its child process."""

    returncode = None

    def poll(self):
        return None


def test_the_acks_header_and_commit_span_say_what_was_retired(
        tmp_path, monkeypatch, cache):
    monkeypatch.setenv("PILOSA_TPU_MESH_MIN_SLICES", "1")
    monkeypatch.setenv("PILOSA_TPU_SPARSE_UPLOAD", "interpret")
    config = _small(4, 16)
    c = cache(1 << 30)
    s = Server(str(tmp_path / "s"), host="127.0.0.1:0",
               anti_entropy_interval=0, polling_interval=0)
    s.open()
    conn = http.client.HTTPConnection(s.host, timeout=30)
    try:
        s.executor._mesh = mesh_mod.make_mesh(1)
        ref = Reference(39, config)
        bench_server.load(bench_server.Http(s.host, _Alive()), ref, config)

        def post(pql, trace=False):
            conn.request("POST", f"/index/{config['index']}/query"
                         + ("?trace=1" if trace else ""), pql.encode())
            resp = conn.getresponse()
            body = json.loads(resp.read())
            return (body["results"],
                    json.loads(resp.getheader("X-Pilosa-Stats")),
                    resp.getheader("X-Pilosa-Query-Id"))

        read = Op({}, (1, 2), None, 'Count(Intersect(Bitmap(frame="f",'
                  ' rowID=1), Bitmap(frame="f", rowID=2)))')
        assert post(read.pql)[0] == [ref.count_intersect((1, 2))]
        col = _new_col(ref, (2, 1))
        results, stats, qid = post(
            f'SetBit(frame="f", rowID=2, columnID={col})', trace=True)
        assert results == [True] and stats["retiredSlabs"] == 1
        events = json.loads(_get(s.host, f"/debug/traces/{qid}"))
        commit = next(e for e in events["traceEvents"]
                      if e["name"] == "commit")
        assert commit["args"]["retired_slabs"] == 1
        assert post(read.pql)[0] == [ref.count_intersect((1, 2)) + 1]
        cache_vars = json.loads(_get(s.host, "/debug/vars"))[
            "deviceBlockCache"]
        assert cache_vars["retired"]["byWrite"] == 1
        assert cache_vars["refills"] == 1 == c.refills
    finally:
        conn.close()
        s.close()


def _get(host: str, path: str) -> str:
    conn = http.client.HTTPConnection(host, timeout=30)
    try:
        conn.request("GET", path)
        return conn.getresponse().read()
    finally:
        conn.close()


# -- (e) the readers, on hand-made surfaces ------------------------------------

def _cache_vars(**kw) -> dict:
    return {"deviceBlockCache": dict({"hits": 0, "misses": 0, "fills": 0,
                                      "fillWaits": 0}, **kw)}


def _record(write: bool, ok: bool = True) -> loadgen.Record:
    rec = loadgen.Record(Op({}, (1,), 5 if write else None, "x"))
    rec.status, rec.results, rec.done = (200 if ok else 500), [True], 0.0
    rec.stats = {} if write else {"devicePrograms": 1}
    return rec


def _run(before, after, records):
    run = run_cell.Run()
    run.config = CONFIG
    run.records = list(records)
    for name, v in (("before", before), ("after", after)):
        setattr(run, name,
                None if v is None else {"status": {}, "vars": dict(v)})
    return run


def _retired(by_write: int) -> dict:
    return {"byWrite": by_write, "byWriteBytes": by_write * 4, "all": 0}


FOUR_ACKS = [_record(True)] * 4 + [_record(True, ok=False)] + \
    [_record(False)] * 20

READER_CASES = [
    # metric, before, after, records, want
    ("write_retired_slabs", _cache_vars(retired=_retired(10)),
     _cache_vars(retired=_retired(13)), FOUR_ACKS, 0.75),
    ("write_retired_slabs", _cache_vars(retired=_retired(0)),
     _cache_vars(retired=_retired(0)), FOUR_ACKS, 0.0),
    # the parent: no counter
    ("write_retired_slabs", _cache_vars(), _cache_vars(), FOUR_ACKS, None),
    ("write_retired_slabs", {}, {}, FOUR_ACKS, None),
    ("write_retired_slabs", None, None, FOUR_ACKS, None),
    # no acknowledged write in the window
    ("write_retired_slabs", _cache_vars(retired=_retired(0)),
     _cache_vars(retired=_retired(2)),
     [_record(True, ok=False), _record(False)], None),
    ("write_refill_pct", _cache_vars(fills=100, refills=4),
     _cache_vars(fills=300, refills=14), FOUR_ACKS, 5.0),
    # the parent: no counter
    ("write_refill_pct", _cache_vars(fills=100), _cache_vars(fills=300),
     FOUR_ACKS, None),
    ("write_refill_pct", None, None, FOUR_ACKS, None),
    # the window filled nothing
    ("write_refill_pct", _cache_vars(fills=100, refills=4),
     _cache_vars(fills=100, refills=4), FOUR_ACKS, None),
]


@pytest.mark.parametrize(
    "metric, before, after, records, want", READER_CASES,
    ids=[f"{c[0]}-{i}" for i, c in enumerate(READER_CASES)])
def test_write_readers(metric, before, after, records, want):
    got = run_cell._reader(metric)(_run(before, after, records))
    assert got == (want if want is None else pytest.approx(want))


def test_a_single_flight_fill_still_holds_under_a_write(loaded):
    """Eight readers of the same two cold rows while one writer sets new
    bits in one of them: every read is one of the two answers its
    moment allows, and the read after the last ack is exact."""
    ex, holder, c, ref = loaded
    want0 = ref.count_intersect((4, 5))
    cols = [x for x in range(2 * SLICE_WIDTH, 3 * SLICE_WIDTH)
            if not ref.bit(5, x) and ref.bit(4, x)][:6]
    assert len(cols) == 6
    errors = []

    def read():
        for _ in range(5):
            got = _count(ex, (4, 5))[0]
            if not want0 <= got <= want0 + len(cols):
                errors.append(got)

    threads = [threading.Thread(target=read) for _ in range(8)]
    for t in threads:
        t.start()
    for col in cols:
        assert _setbit(ex, 5, col)[0] is True
        time.sleep(0.01)
    for t in threads:
        t.join(JOIN_S)
        assert not t.is_alive()
    assert not errors
    assert _count(ex, (4, 5))[0] == want0 + len(cols)
    used = c.snapshot()
    assert used["usedBytes"] == used["entries"] * 4 * SLAB_ROW
