"""The deployment ``baseline-c4-share8`` (ISSUE 34) at a small size: one
chip's share of an index whose slice axis eight chips share, under Zipf
keys with the working set at twice the residency budget, so that the
cold side of a read — LRU and eviction, the single-flight fill, the
sparse upload and the on-device densify — does most of the work.

The benchmark's cell ``share8-count-zipf`` holds it on the chip at
32 slices x 512 rows against the program's 1 GiB; here the
configuration's own generator makes 4 slices x 64 rows, the budget is
half the slabs, and the cell's own traffic (``count-zipf099``) goes
through a real server on a real socket. What the cell's five per-layer
metrics rest on is tested beside it: a request says in one response
header whether it found every leaf resident, filled some itself or
waited for another request's fill, and the cache counts the same.
"""

import http.client
import json
import os
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from cellbench import run_cell
from cellbench.lib import bytes_fns, fill_bytes, loadgen
from cellbench.lib import server as bench_server
from cellbench.lib.data import SLICE_WIDTH, Reference
from cellbench.lib.traffic import Generator, Op
from pilosa_tpu.executor import ExecOptions, Executor
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.obs import accounting
from pilosa_tpu.ops import packed
from pilosa_tpu.parallel import mesh as mesh_mod
from pilosa_tpu.parallel import residency
from pilosa_tpu.sched import QueryContext, Warmup
from pilosa_tpu.sched import context as sched_context
from pilosa_tpu.server.server import Server

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "share8-count-zipf"
BENCH, ENTRY, CONFIG, TRAFFIC = run_cell.resolve(CELL)
SLAB_ROW = packed.WORDS_PER_SLICE * 4      # one slice of one row, dense
JOIN_S = 60.0

NEW_METRICS = {
    # name: (reader module, layer, unit, better, source, moves)
    "resident_read_pct": ("resident_read", "residency", "%", "higher",
                          "program_counter", "read_p50_ms"),
    "cold_fill_ms": ("cold_fill", "residency", "ms", "lower",
                     "program_counter", "read_p95_ms"),
    "cold_pack_ms": ("cold_pack", "residency", "ms", "lower",
                     "program_counter", "read_p95_ms"),
    "cold_upload_ms": ("cold_upload", "device programs", "ms", "lower",
                       "program_counter", "read_p95_ms"),
    "cold_kernels_roofline": ("cold_kernels_roofline", "kernels", "%",
                              "higher", "device_trace", "read_p50_ms"),
}


def _small(n_slices: int, n_rows: int) -> dict:
    return dict(CONFIG, n_slices=n_slices, n_rows=n_rows)


@pytest.fixture
def cache(monkeypatch):
    """A residency cache of this test's own (the process-wide one
    carries other tests' slabs and counters)."""
    def install(budget_bytes: int):
        c = residency.DeviceBlockCache(budget_bytes)
        monkeypatch.setattr(residency, "_device_cache", c)
        return c
    return install


def _get(host: str, path: str) -> dict:
    conn = http.client.HTTPConnection(host, timeout=30)
    try:
        conn.request("GET", path)
        return json.loads(conn.getresponse().read())
    finally:
        conn.close()


class _Alive:
    """What ``cellbench.lib.server.Http`` asks of its child process."""

    returncode = None

    def poll(self):
        return None


# -- (a) the deployment through the served path -------------------------------

@pytest.fixture
def served(tmp_path, monkeypatch, cache):
    """The configuration's own generator at 4 slices x 64 rows, loaded
    over the import routes into a real server that meshes one device
    (one chip's share), the sparse upload in interpret mode, a budget of
    half the 64 slabs."""
    monkeypatch.setenv("PILOSA_TPU_MESH_MIN_SLICES", "1")
    monkeypatch.setenv("PILOSA_TPU_SPARSE_UPLOAD", "interpret")
    config = _small(4, 64)
    c = cache(32 * 4 * SLAB_ROW)
    s = Server(str(tmp_path / "s"), host="127.0.0.1:0",
               anti_entropy_interval=0, polling_interval=0)
    s.open()
    try:
        s.executor._mesh = mesh_mod.make_mesh(1)
        ref = Reference(34, config)
        bench_server.load(bench_server.Http(s.host, _Alive()), ref, config)
        yield s, c, ref, config
    finally:
        s.close()


def test_zipf_reads_over_twice_the_budget_are_exact_and_accounted(served):
    """A few hundred ``count-zipf099`` requests from 8 threads: every
    answer is the reference's, the LRU evicts and re-fills, and what the
    responses say of their cold leaves adds up to what the cache
    counted — the equality ``resident_read_pct`` rests on."""
    s, c, ref, config = served
    gen = Generator(TRAFFIC, config, 34)
    records, _, _ = loadgen.drive(s.host, config["index"], gen,
                                  int(TRAFFIC["arrival"]["clients"]),
                                  requests=300)
    assert len(records) == 300 and all(r.ok for r in records)
    for r in records:
        assert r.results == [ref.count_intersect(r.op.rows)], r.op.pql
        assert r.stats["devicePrograms"] == 1
    asked = {row for r in records for row in r.op.rows}
    snap = _get(s.host, "/debug/vars")["deviceBlockCache"]
    assert snap["evictions"] > 0
    assert snap["usedBytes"] <= snap["budgetBytes"]
    assert snap["fills"] > len(asked)           # evicted rows came back
    assert sum(r.stats.get("coldLeaves", 0)
               for r in records) == snap["fills"] == c.fills
    assert sum(r.stats.get("fillWaits", 0)
               for r in records) == snap["fillWaits"]
    assert snap["misses"] == snap["fills"] + snap["fillWaits"]
    # every slab is 4 slices of one row at its uploaded shape
    assert snap["fillBytes"] == snap["fills"] * 4 * SLAB_ROW
    # the dense head of the density law packs on the host, the sparse
    # tail densifies on the device: both transfers ran
    assert 0 < snap["fillsDense"] < snap["fills"]
    cold = sum(1 for r in records if r.stats.get("coldLeaves"))
    assert 0 < cold < len(records)


def test_one_header_tells_resident_filling_and_waiting_reads_apart(served):
    """``coldLeaves`` = built it, ``fillWaits`` = waited for a build,
    neither = every leaf resident; the kept trace's ``pack`` and
    ``upload`` spans say which rows, how many slices and bytes, and
    which transfer."""
    s, c, ref, config = served
    conn = http.client.HTTPConnection(s.host, timeout=30)

    def post(rows, trace=False):
        op = Op({}, rows, None, "Count(Intersect(%s))" % ", ".join(
            f'Bitmap(frame="f", rowID={r})' for r in rows))
        conn.request("POST", f"/index/{config['index']}/query"
                     + ("?trace=1" if trace else ""), op.pql.encode())
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert body["results"] == [ref.count_intersect(rows)]
        return (json.loads(resp.getheader("X-Pilosa-Stats")),
                resp.getheader("X-Pilosa-Query-Id"))

    try:
        stats, qid = post((0, 40), trace=True)      # both cold
        assert stats["coldLeaves"] == 2 and "fillWaits" not in stats
        events = _get(s.host, f"/debug/traces/{qid}")["traceEvents"]
        fills = {}
        for e in events:
            if e["name"] in ("pack", "upload"):
                fills.setdefault(e["args"]["row"], {})[e["name"]] = e["args"]
        for row, path, nbytes in ((0, "dense", 4 * SLAB_ROW),
                                  (40, "sparse", None)):
            for stage in ("pack", "upload"):
                args = fills[row][stage]
                assert args["path"] == path and args["slices"] == 4
                assert args["bytes"] == (nbytes or args["bytes"]) > 0
            assert fills[row]["pack"]["bytes"] == \
                fills[row]["upload"]["bytes"]
            # how many containers the one pass took: the pack's alone
            assert 4 <= fills[row]["pack"]["containers"] <= 4 * 16
            assert "containers" not in fills[row]["upload"]
        assert fills[40]["pack"]["bytes"] < 4 * SLAB_ROW // 2
        span = next(e for e in events if e["name"] == "map_reduce")
        assert span["args"]["cold_leaves"] == 2
        assert span["args"]["fill_waits"] == 0
        stats, _ = post((40, 0))                    # both resident
        assert "coldLeaves" not in stats and "fillWaits" not in stats
        stats, _ = post((0, 41))                    # one of each
        assert stats["coldLeaves"] == 1 and "fillWaits" not in stats
        assert (c.fills, c.fills_dense, c.fill_waits) == (3, 1, 0)
    finally:
        conn.close()


def test_a_waiting_request_is_marked_and_a_resident_one_pays_nothing():
    """Two requests ask for one cold key: the first builds (its ledger
    says ``coldLeaves``), the second waits (``fillWaits``), a third
    finds it resident and its ledger stays empty."""
    c = residency.DeviceBlockCache(1 << 20)
    building, release = threading.Event(), threading.Event()
    costs = {}

    def build():
        building.set()
        assert release.wait(JOIN_S)
        return jnp.zeros(8, dtype=jnp.uint32)

    def ask(name):
        ctx = QueryContext(pql=name)
        costs[name] = accounting.attach(ctx)
        with sched_context.use(ctx):
            c.get_or_build(("k",), build)

    first = threading.Thread(target=ask, args=("builder",))
    first.start()
    assert building.wait(JOIN_S)
    second = threading.Thread(target=ask, args=("waiter",))
    second.start()
    deadline = time.monotonic() + JOIN_S
    while c.fill_waits < 1:
        assert second.is_alive() and time.monotonic() < deadline
        time.sleep(0.01)
    release.set()
    for t in (first, second):
        t.join(JOIN_S)
        assert not t.is_alive()
    ask("resident")
    got = {name: cost.summary() for name, cost in costs.items()}
    assert got["builder"]["coldLeaves"] == 1
    assert "fillWaits" not in got["builder"]
    assert got["waiter"]["fillWaits"] == 1
    assert "coldLeaves" not in got["waiter"]
    assert costs["waiter"].to_tree()["fillWaits"] == 1
    assert "coldLeaves" not in got["resident"]
    assert "fillWaits" not in got["resident"]
    snap = c.snapshot()
    assert (snap["fills"], snap["fillWaits"], snap["hits"]) == (1, 1, 1)
    assert snap["fillBytes"] == 32 and snap["fillsDense"] == 0


# -- (b) the share adds up ----------------------------------------------------

SHARE_QUERIES = [(0, 1), (2, 5), (0, 3, 9), (1, 4, 6, 11), (7, 15)]


def test_the_eight_shares_partial_counts_add_up_to_the_whole(tmp_path):
    """One small index of 8 slices, one slice a share: each share is
    loaded into an index of the share's own shape (its slice rebased to
    0, as ``baseline-c4-share8`` is an index of its own 32 slices) and
    answers a partial count through the device path; the eight partial
    counts sum to the uncut reference's answer."""
    whole = Reference(8, _small(8, 16))
    partial = {q: 0 for q in SHARE_QUERIES}
    for share in range(8):
        holder = Holder(str(tmp_path / f"share{share}"))
        holder.open()
        rows, cols = whole.slice_positions(share, share + 1)
        frame = holder.create_index("i").create_frame("f")
        frame.import_bits(rows, cols - np.uint64(share * SLICE_WIDTH))
        ex = Executor(holder, host="local", use_mesh=True,
                      mesh_min_slices=1)
        ex._mesh = mesh_mod.make_mesh(1)
        try:
            assert holder.index("i").max_slice() == 0
            for q in SHARE_QUERIES:
                ctx = QueryContext()
                accounting.attach(ctx)
                pql = "Count(Intersect(%s))" % ", ".join(
                    f"Bitmap(frame=f, rowID={r})" for r in q)
                partial[q] += ex.execute("i", pql, None,
                                         ExecOptions(ctx=ctx))[0]
                assert ctx.cost.device_programs == 1
        finally:
            ex.close()
            holder.close()
    for q in SHARE_QUERIES:
        assert partial[q] == whole.count_intersect(q), q
    assert any(partial.values())


# -- (c) the readers, on hand-made surfaces -----------------------------------

def _cache_vars(**kw) -> dict:
    return {"deviceBlockCache": dict(
        {"hits": 0, "misses": 0, "fills": 0, "fillWaits": 0,
         "fillSeconds": 0.0}, **kw)}


def _stages(requests: int, **wall_us) -> dict:
    return {"queryStages": {"read": {
        "requests": requests, "cpuUs": 0, "offThreadCpuUs": 0,
        "stages": {k: {"n": requests, "wallUs": v}
                   for k, v in wall_us.items()},
        "offThread": {}}}}


def _record(k: int = 2, done: float = 0.0, **stats) -> loadgen.Record:
    rec = loadgen.Record(Op({"bytes_fn": "dense_leaves"},
                            tuple(range(k)), None, "Count(...)"))
    rec.status, rec.results, rec.done = 200, [0], done
    rec.stats = dict({"devicePrograms": 1}, **stats)
    return rec


def _run(before=None, after=None, records=(), trace=None):
    run = run_cell.Run()
    run.config = CONFIG
    run.records = list(records)
    for name, v in (("before", before), ("after", after)):
        setattr(run, name,
                None if v is None else {"status": {}, "vars": dict(v)})
    run.trace = trace
    run.peak = {"hbm_bytes_per_s": 819e9}
    return run


def _reader(metric: str):
    return run_cell._reader(metric)


TEN = [_record(coldLeaves=2), _record(coldLeaves=1), _record(fillWaits=1),
       _record(coldLeaves=1, fillWaits=1)] + [_record() for _ in range(6)]

READER_CASES = [
    # metric, before, after, records, want
    ("resident_read_pct", _cache_vars(), _cache_vars(fillWaits=2), TEN,
     60.0),
    ("resident_read_pct", _cache_vars(), _cache_vars(fillWaits=0),
     [_record(coldLeaves=1), _record(), _record(), _record()], 75.0),
    # host-served reads looked nothing up: on neither side
    ("resident_read_pct", _cache_vars(), _cache_vars(),
     [_record(), _record(devicePrograms=0)], 100.0),
    # the parent: its cache counted waits, no response carried the key
    ("resident_read_pct", _cache_vars(), _cache_vars(fillWaits=3),
     [_record(coldLeaves=1), _record(), _record()], None),
    ("resident_read_pct", {}, {}, TEN, None),
    ("resident_read_pct", None, None, TEN, None),
    ("resident_read_pct", _cache_vars(), _cache_vars(),
     [_record(devicePrograms=0)], None),
    ("cold_fill_ms", _cache_vars(fills=10, fillSeconds=4.0),
     _cache_vars(fills=30, fillSeconds=12.0), TEN, 400.0),
    ("cold_fill_ms", _cache_vars(fills=10, fillSeconds=4.0),
     _cache_vars(fills=10, fillSeconds=4.0), TEN, None),
    ("cold_fill_ms", {"deviceBlockCache": {"hits": 1}},
     {"deviceBlockCache": {"hits": 9}}, TEN, None),
    ("cold_fill_ms", None, None, TEN, None),
    ("cold_pack_ms",
     dict(_cache_vars(fills=4), **_stages(100, pack=1_000_000)),
     dict(_cache_vars(fills=9), **_stages(110, pack=2_500_000)), TEN,
     300.0),
    ("cold_upload_ms",
     dict(_cache_vars(fills=4), **_stages(100, upload=10_000)),
     dict(_cache_vars(fills=9), **_stages(110, upload=60_000)), TEN, 10.0),
    # zero fills in the window
    ("cold_pack_ms", dict(_cache_vars(fills=4), **_stages(100, pack=5)),
     dict(_cache_vars(fills=4), **_stages(110, pack=5)), TEN, None),
    # a program without the stage clock, or without the fill counter
    ("cold_pack_ms", _cache_vars(fills=4), _cache_vars(fills=9), TEN,
     None),
    ("cold_upload_ms", dict({"deviceBlockCache": {}}, **_stages(100)),
     dict({"deviceBlockCache": {}}, **_stages(110, upload=7)), TEN, None),
    # the clock folded another count of requests than the answered reads
    ("cold_pack_ms", dict(_cache_vars(fills=4), **_stages(100, pack=1)),
     dict(_cache_vars(fills=9), **_stages(111, pack=9)), TEN, None),
    ("cold_upload_ms", None, None, TEN, None),
]


@pytest.mark.parametrize(
    "metric, before, after, records, want", READER_CASES,
    ids=[f"{c[0]}-{i}" for i, c in enumerate(READER_CASES)])
def test_cold_path_readers(metric, before, after, records, want):
    got = _reader(metric)(_run(before, after, records))
    assert got == (want if want is None else pytest.approx(want))


# -- (e) the roofline share on a synthetic trace ------------------------------

SLICE = {"t0": 100.0, "t1": 103.0, "busy_s": 0.001, "window_s": 3.0,
         "device_ops": [], "idle_gaps": []}


def _roofline(records, busy_s=0.001, before=None, after=None):
    trace = dict(SLICE, busy_s=busy_s)
    return _reader("cold_kernels_roofline")(
        _run(before, after, records, trace))


def test_cold_kernels_roofline_on_a_trace_with_known_bytes_and_time():
    n = CONFIG["n_slices"] * bytes_fns.SLICE_ROW_BYTES      # one slab
    assert n == 4 << 20
    assert fill_bytes.cold_leaves({"coldLeaves": 3}, CONFIG) == 3 * n
    assert fill_bytes.cold_leaves({"fillWaits": 2}, CONFIG) == 0
    inside = [_record(2, done=101.0),                       # 2 slabs read
              _record(3, done=102.0, coldLeaves=2),         # 3 read + 2 built
              _record(4, done=101.5, fillWaits=1)]          # 4 read
    outside = [_record(4, done=99.0, coldLeaves=4),
               _record(2, done=101.0, devicePrograms=0),    # host-served
               _record(2, done=104.0)]
    want = 100.0 * (11 * n / 819e9) / 0.001
    assert _roofline(inside + outside) == pytest.approx(want)
    # the resident side alone is kernels_roofline's reading
    assert _roofline(inside[:1] + inside[2:]) == pytest.approx(
        _reader("kernels_roofline")(_run(
            None, None, inside[:1] + inside[2:], SLICE)))
    # at the roofline: 11 slabs in exactly the time the HBM needs
    assert _roofline(inside, busy_s=11 * n / 819e9) == pytest.approx(100.0)
    # a fill the host packed dense wrote its slab outside ``XLA Ops``:
    # a quarter of the window's fills were such, so a quarter of the
    # fill bytes is not counted
    before = _cache_vars(fills=8, fillsDense=8)
    after = _cache_vars(fills=16, fillsDense=10)
    assert _roofline(inside, before=before, after=after) == pytest.approx(
        100.0 * ((9 + 2 * 0.75) * n / 819e9) / 0.001)
    # the parent counts no ``fillsDense``: every fill counts
    assert _roofline(inside, before=_cache_vars(fills=8),
                     after=_cache_vars(fills=16)) == pytest.approx(want)


@pytest.mark.parametrize("records, trace", [
    ([_record(2, done=101.0)], None),                        # untraced
    ([_record(2, done=101.0)], dict(SLICE, busy_s=0.0)),     # no device op
    ([_record(2, done=99.0)], SLICE),                        # none inside
    ([_record(2, done=101.0, devicePrograms=0)], SLICE),     # host-served
], ids=["untraced", "idle", "outside", "host"])
def test_cold_kernels_roofline_is_silent_with_nothing_to_read(records,
                                                              trace):
    assert _reader("cold_kernels_roofline")(
        _run(None, None, records, trace)) is None


# -- (d) the files ------------------------------------------------------------

def test_the_cell_and_its_configuration_are_what_the_issue_names():
    assert {k: ENTRY[k] for k in ("name", "config", "traffic", "chips")} \
        == {"name": CELL, "config": "baseline-c4-share8",
            "traffic": "count-zipf099", "chips": 1}
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "baseline-c4-share8")
    assert entry["file"] == "cellbench/configs/baseline-c4-share8.json"
    assert entry["source"] == CONFIG["source"]
    assert entry["reduced"] == list(CONFIG["reduced"]) == ["n_slices"]
    assert CONFIG["reduced"]["n_slices"] == [256, 32]
    assert (CONFIG["n_slices"], CONFIG["n_rows"]) == (32, 512)
    # the density law and the guarantees of the uncut configuration
    whole = run_cell.resolve("c4-count-hot")[2]
    for key in ("d0", "zipf_s", "run_rows", "guarantees"):
        assert CONFIG[key] == whole[key], key
    # the mix is c4-count-hot's own op classes under another key law
    assert TRAFFIC["ops"] == run_cell.resolve("c4-count-hot")[3]["ops"]
    assert TRAFFIC["keys"] == {"law": "zipf", "s": 0.99}
    assert TRAFFIC["arrival"] == {"loop": "closed", "clients": 8}
    # twice the budget the program has
    slabs = CONFIG["n_rows"] * CONFIG["n_slices"] * SLAB_ROW
    assert slabs == 2 * (residency.DEFAULT_HBM_BUDGET_MB << 20)
    for e in (entry, ENTRY):
        assert 0 < len(e["why"]) <= 200


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_metric_file_agrees_with_benchmark_json(name):
    reader, layer, unit, better, source, moves = NEW_METRICS[name]
    with open(os.path.join(ROOT, "cellbench", "metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
    for k, v in entry.items():
        assert k == "workloads" or spec[k] == v, k
    assert entry["workloads"] == [CELL]
    assert (entry["layer"], entry["unit"], entry["better"],
            entry["source"], entry["moves"]) == (layer, unit, better,
                                                 source, moves)
    assert spec["reader"] == reader and spec["what"]
    assert callable(_reader(name))
    # a layer the benchmark already names, letter for letter
    assert layer in {m["layer"] for m in BENCH["per_layer"]
                     if m["name"] not in NEW_METRICS}
    # the cell reports the end-to-end metric each of its metrics moves
    e2e = next(m for m in BENCH["end_to_end"] if m["name"] == moves)
    assert "workloads" not in e2e


def test_the_cell_reports_the_unlisted_metrics_and_its_own_five():
    """And the four of the interpreter (PR 36), which list every cell."""
    listed = {m["name"] for m in run_cell._listed(BENCH["per_layer"], CELL)}
    assert listed == set(NEW_METRICS) | {
        "device_served_pct", "compiles_in_window", "device_idle_pct",
        "import_mbit_s", "gc_pause_pct", "wake_late_ms", "stall_pct",
        "stall_named_pct"}


# -- the densify programs are warm before the first fill ----------------------

def _rows(n: int, width: int) -> list:
    """``n`` slice-rows whose fullest 128-word group holds ``width``
    set words: the gate buckets them at exactly that width."""
    words = np.zeros(packed.WORDS_PER_SLICE, dtype=np.uint32)
    words[:width] = 1
    return [packed.unpack_to_bitmap(words)] * n


def _sparse(n: int, width: int) -> tuple:
    return packed.pack_slab(_rows(n, width))[0]


def test_the_gate_passes_no_width_the_warm_up_does_not_compile():
    for width in mesh_mod.DENSIFY_WIDTHS:
        assert _sparse(4, width)[0].shape == (
            4, packed.WORDS_PER_SLICE // 128, width)
    for width in (3, 5, 17):        # padded up to the next power of two
        assert _sparse(4, width)[0].shape[-1] in mesh_mod.DENSIFY_WIDTHS
    sparse, block, _ = packed.pack_slab(_rows(4, 33))
    assert sparse is None and int(block.sum()) == 4 * 33


def test_after_warm_up_a_first_sparse_fill_of_each_width_compiles_nothing(
        tmp_path, monkeypatch, cache):
    """The start-up pass compiles the densify program of every width at
    the holder's slab shape; a slab shape it did not know (an index
    that grew into the next bucket) is heard of from its first fill and
    its other widths are compiled by the lane, not by later fills."""
    monkeypatch.setenv("PILOSA_TPU_SPARSE_UPLOAD", "interpret")
    cache(1 << 30)
    holder = Holder(str(tmp_path / "h"))
    holder.open()
    frame = holder.create_index("i").create_frame("f")
    cols = np.arange(2, dtype=np.uint64) * np.uint64(SLICE_WIDTH)
    frame.import_bits(np.zeros(2, dtype=np.uint64), cols)
    ex = Executor(holder, host="local", use_mesh=True, mesh_min_slices=1)
    ex._mesh = mesh_mod.make_mesh(1)
    warm = Warmup(ex)
    warm.start()
    try:
        warm.wait(JOIN_S)
        status = warm.to_json()
        assert status["state"] == "done", status
        assert status["bucket"] == 2 and status["densified"] == [[2]]
        compiled = mesh_mod.compile_stats()["firstCalls"]
        for width in mesh_mod.DENSIFY_WIDTHS:
            out = mesh_mod.densify_sharded(
                ex._mesh, *_sparse(2, width), interpret=True)
            assert int(np.asarray(out).sum()) == 2 * width
        assert mesh_mod.compile_stats()["firstCalls"] == compiled
        # the index grows to 4 slices: the first fill compiles its own
        # width, the lane the other five
        mesh_mod.densify_sharded(ex._mesh, *_sparse(4, 4), interpret=True)
        deadline = time.monotonic() + JOIN_S
        while [4] not in warm.to_json()["densified"]:
            assert warm._thread.is_alive() and time.monotonic() < deadline
            time.sleep(0.05)
        compiled = mesh_mod.compile_stats()["firstCalls"]
        for width in mesh_mod.DENSIFY_WIDTHS:
            mesh_mod.densify_sharded(
                ex._mesh, *_sparse(4, width), interpret=True)
        assert mesh_mod.compile_stats()["firstCalls"] == compiled
    finally:
        warm.stop()
        warm._thread.join(JOIN_S)
        assert not warm._thread.is_alive()
        assert mesh_mod.on_densify is None
        ex.close()
        holder.close()
