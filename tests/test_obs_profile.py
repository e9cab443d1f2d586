"""Per-query cost accounting (obs.accounting), the continuous profiler
(obs.profile), and SLO health (obs.slo): ledger units, profile-ring
bounds, the ?profile=1 cost tree over HTTP, /health readiness, the
wire-import stage breakdown, and the overhead guard proving
accounting + the default-rate profiler cost <5% on the query p50."""

import io
import json
import threading
import time

import numpy as np
import pytest

from pilosa_tpu.executor import Executor
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.obs import accounting
from pilosa_tpu.obs.profile import ContinuousProfiler
from pilosa_tpu.obs.slo import HealthChecker, SLOTracker
from pilosa_tpu.obs import metrics as obs_metrics
from pilosa_tpu.sched import QueryContext
from pilosa_tpu.sched import context as sched_context
from pilosa_tpu.server.handler import Handler


def call(app, method, path, body=b"", content_type="", headers=None):
    if "?" in path:
        path, _, qs = path.partition("?")
    else:
        qs = ""
    environ = {
        "REQUEST_METHOD": method,
        "PATH_INFO": path,
        "QUERY_STRING": qs,
        "CONTENT_LENGTH": str(len(body)),
        "wsgi.input": io.BytesIO(body),
    }
    if content_type:
        environ["CONTENT_TYPE"] = content_type
    for k, v in (headers or {}).items():
        environ["HTTP_" + k.upper().replace("-", "_")] = v
    out = {}

    def start_response(status, hs):
        out["status"] = int(status.split()[0])
        out["headers"] = dict(hs)

    chunks = app(environ, start_response)
    return out["status"], out["headers"], b"".join(chunks)


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "data"))
    h.open()
    yield h
    h.close()


@pytest.fixture
def handler(holder):
    ex = Executor(holder, host="local", use_mesh=False)
    yield Handler(holder, ex, host="local")
    ex.close()


def _two_row_frame(holder, n=400):
    frame = holder.create_index_if_not_exists("i") \
        .create_frame_if_not_exists("f")
    rows = np.concatenate([np.zeros(n, np.uint64),
                           np.ones(n, np.uint64)])
    cols = np.concatenate([np.arange(n, dtype=np.uint64),
                           np.arange(n // 2, n + n // 2,
                                     dtype=np.uint64)])
    frame.import_bits(rows, cols)
    return frame


# -- ledger units -------------------------------------------------------------

class TestQueryCostLedger:
    def test_note_sites_accumulate(self):
        cost = accounting.QueryCost(node="n1")
        cost.note_container_op("intersect", "array_array", words=8)
        cost.note_container_op("intersect", "array_array", words=8)
        cost.note_container_op("union", "bitmap_bitmap", words=2048)
        cost.note_bits_written(5)
        cost.note_device_dispatch(1 << 20)
        cost.note_compile(0.25)
        cost.note_rpc("peer:1", 100, 900)
        cost.note_rpc("peer:1", 50, 450)
        tree = cost.to_tree({"execute": 0.5, "admission": 0.001})
        assert tree["containerOps"] == {"intersect:array_array": 2,
                                        "union:bitmap_bitmap": 1}
        assert tree["wordsScanned"] == 8 + 8 + 2048
        assert tree["bitsWritten"] == 5
        assert tree["devicePrograms"] == 1
        assert tree["deviceBytes"] == 1 << 20
        assert tree["compileMs"] == 250.0
        assert tree["rpc"]["peer:1"] == {"bytesOut": 150,
                                         "bytesIn": 1350, "calls": 2}
        assert tree["queueWaitMs"] == 1.0
        summary = cost.summary()
        assert summary["containerOps"] == 3
        assert summary["rpcBytesOut"] == 150
        assert summary["rpcBytesIn"] == 1350

    def test_current_cost_requires_bound_ctx(self):
        assert accounting.current_cost() is None
        ctx = QueryContext(pql="q")
        assert accounting.attach(ctx) is not None
        with sched_context.use(ctx):
            assert accounting.current_cost() is ctx.cost
        assert accounting.current_cost() is None

    def test_attach_respects_switch(self):
        accounting.set_enabled(False)
        try:
            ctx = QueryContext(pql="q")
            assert accounting.attach(ctx) is None
            assert ctx.cost is None
        finally:
            accounting.set_enabled(True)

    def test_remote_stitch_and_child_cap(self):
        cost = accounting.QueryCost(node="coord")
        child = accounting.QueryCost(node="peer")
        child.note_container_op("intersect", "bitmap_bitmap", 2048)
        cost.add_remote_json(child.wire_json())
        cost.add_remote_json("not json")       # ignored
        cost.add_remote_json("[1, 2, 3]")      # wrong shape, ignored
        tree = cost.to_tree()
        assert len(tree["children"]) == 1
        assert tree["children"][0]["node"] == "peer"
        assert tree["children"][0]["containerOps"] == {
            "intersect:bitmap_bitmap": 1}
        for i in range(2 * accounting.MAX_CHILDREN):
            cost.add_remote_json(json.dumps({"node": f"p{i}"}))
        assert len(cost.to_tree()["children"]) \
            == accounting.MAX_CHILDREN

    def test_wire_json_respects_header_budget(self):
        cost = accounting.QueryCost(node="n" * 40)
        for i in range(4000):
            cost.note_container_op(f"op{i}", "array_array", 1)
        wire = cost.wire_json()
        assert len(wire) <= accounting.QueryCost._WIRE_BYTES
        tree = json.loads(wire)
        # Over budget the mix collapses to its total — never dropped.
        assert tree["containerOps"] == {"total": 4000}

    def test_wide_fanout_attributes_reduce_side_ops(self, holder):
        """The chunked slice fan-out pre-reduces inside pool tasks;
        the ctx binding must cover map AND reduce there — a wide query
        whose merges went unattributed would undercount exactly the
        queries the ledger exists to explain."""
        from pilosa_tpu.executor import ExecOptions, Executor
        frame = holder.create_index_if_not_exists("w") \
            .create_frame_if_not_exists("f")
        rng = np.random.default_rng(3)
        n_slices = 64  # >> 4 * max_workers → chunk > 1
        from pilosa_tpu import SLICE_WIDTH
        for row in (0, 1):
            cols = (rng.integers(0, SLICE_WIDTH, size=20 * n_slices)
                    + np.repeat(np.arange(n_slices), 20) * SLICE_WIDTH)
            frame.import_bits(np.full(len(cols), row, np.uint64),
                              cols.astype(np.uint64))
        ex = Executor(holder, host="local", use_mesh=False)
        q = ('Intersect(Bitmap(frame=f, rowID=0),'
             ' Bitmap(frame=f, rowID=1))')
        ex.execute("w", q)  # warm
        ex._bitmap_results.clear()
        ctx = QueryContext(pql=q)
        accounting.attach(ctx)
        ex.execute("w", q, opt=ExecOptions(ctx=ctx))
        # At least one container op per slice leg reached the ledger.
        assert sum(ctx.cost.container_ops.values()) >= n_slices
        ex.close()

    def test_roaring_ops_attribute_to_bound_query(self):
        from pilosa_tpu.storage import roaring
        ctx = QueryContext(pql="q")
        accounting.attach(ctx)
        a = roaring.Bitmap(*range(0, 130000, 2))   # bitmap container
        b = roaring.Bitmap(1, 2, 3)                # array container
        with sched_context.use(ctx):
            a.intersect(b)
        key = "intersect:array_bitmap"
        assert ctx.cost.container_ops.get(key) == 1
        assert ctx.cost.words_scanned >= 1024  # the bitmap operand


# -- continuous profiler ------------------------------------------------------

class TestContinuousProfiler:
    def test_ring_is_bounded(self):
        prof = ContinuousProfiler(hz=100, ring=32)
        stop = threading.Event()

        def busy_loop_for_profiler():
            while not stop.is_set():
                sum(i * i for i in range(200))

        t = threading.Thread(target=busy_loop_for_profiler, daemon=True)
        t.start()
        try:
            for _ in range(100):
                prof.sample_once()
        finally:
            stop.set()
            t.join()
        snap = prof.snapshot()
        assert snap["ringSamples"] <= 32
        assert snap["ticks"] == 100
        assert not prof.running  # sample_once() never started a thread

    def test_query_id_tagged_and_filterable(self):
        prof = ContinuousProfiler(hz=100, ring=1024)
        ctx = QueryContext(pql="q")
        stop = threading.Event()

        def busy_named_query_leg():
            with sched_context.use(ctx):
                while not stop.is_set():
                    sum(i * i for i in range(200))

        t = threading.Thread(target=busy_named_query_leg, daemon=True)
        t.start()
        try:
            time.sleep(0.02)
            for _ in range(20):
                prof.sample_once()
                time.sleep(0.002)
        finally:
            stop.set()
            t.join()
        mine = prof.flame(query=ctx.id)
        assert "busy_named_query_leg" in mine
        # Collapsed-stack format: every non-header line ends in a count.
        for line in mine.splitlines()[1:]:
            stack, _, count = line.rpartition(" ")
            assert stack and count.isdigit()
        # A bogus query id matches nothing.
        none = prof.flame(query="nope")
        assert "busy_named_query_leg" not in none
        assert none.splitlines()[0].startswith(
            "# continuous profile: 0 samples")

    def test_background_thread_start_stop(self):
        prof = ContinuousProfiler(hz=100, ring=64)
        prof.start()
        assert prof.running
        time.sleep(0.08)
        prof.stop()
        assert not prof.running
        assert prof.samples_taken >= 1

    def test_flame_endpoint(self, handler):
        status, _, body = call(handler, "GET", "/debug/pprof/flame")
        assert status == 200
        assert body.decode().startswith("# continuous profile:")
        status, _, _ = call(handler, "GET",
                            "/debug/pprof/flame?since=bogus")
        assert status == 400


# -- ?profile=1 cost tree over HTTP -------------------------------------------

class TestProfileTreeHTTP:
    def test_profile_tree_shape(self, handler, holder):
        _two_row_frame(holder)
        status, headers, body = call(
            handler, "POST", "/index/i/query?profile=1",
            b'Intersect(Bitmap(frame="f", rowID=0),'
            b' Bitmap(frame="f", rowID=1))')
        assert status == 200
        resp = json.loads(body)
        tree = resp["profile"]
        assert tree["node"] == "local"
        assert sum(tree["containerOps"].values()) >= 1
        assert tree["wordsScanned"] > 0
        assert {"parse", "admission", "execute"} <= set(tree["stages"])
        assert "queueWaitMs" in tree
        # The compact roll-up rides EVERY response as X-Pilosa-Stats.
        stats = json.loads(headers["X-Pilosa-Stats"])
        assert stats["containerOps"] \
            == sum(tree["containerOps"].values())

    def test_without_profile_param_no_tree_but_header(self, handler,
                                                      holder):
        _two_row_frame(holder)
        status, headers, body = call(
            handler, "POST", "/index/i/query",
            b'Count(Bitmap(frame="f", rowID=0))')
        assert status == 200
        assert "profile" not in json.loads(body)
        assert "X-Pilosa-Stats" in headers

    def test_debug_queries_slow_log_carries_cost(self, holder):
        from pilosa_tpu.sched import QueryRegistry
        ex = Executor(holder, host="local", use_mesh=False)
        registry = QueryRegistry(slow_threshold_s=1e-9)
        h = Handler(holder, ex, host="local", registry=registry)
        _two_row_frame(holder)
        status, headers, _ = call(
            h, "POST", "/index/i/query",
            b'Intersect(Bitmap(frame="f", rowID=0),'
            b' Bitmap(frame="f", rowID=1))')
        assert status == 200
        qid = headers["X-Pilosa-Query-Id"]
        status, _, body = call(h, "GET", "/debug/queries/slow")
        entry = [e for e in json.loads(body)["slow"]
                 if e["id"] == qid][-1]
        assert entry["cost"]["containerOps"] >= 1
        ex.close()

    def test_write_query_counts_bits_written(self, handler, holder):
        holder.create_index_if_not_exists("i") \
            .create_frame_if_not_exists("f")
        status, headers, _ = call(
            handler, "POST", "/index/i/query",
            b'SetBit(frame="f", rowID=7, columnID=3)')
        assert status == 200
        stats = json.loads(headers["X-Pilosa-Stats"])
        assert stats["bitsWritten"] == 1


# -- wire-import stage breakdown ----------------------------------------------

class TestImportStageTiming:
    def test_decode_apply_recorded(self, handler, holder):
        from pilosa_tpu.proto import internal_pb2 as pb
        holder.create_index_if_not_exists("i") \
            .create_frame_if_not_exists("f")

        def stage_count(stage):
            fam = obs_metrics.IMPORT_STAGE_SECONDS
            _counts, _sum, n = fam.labels(stage).snapshot()
            return n

        before_d, before_a = stage_count("decode"), stage_count("apply")
        req = pb.ImportRequest(Index="i", Frame="f", Slice=0,
                               RowIDs=[1, 1], ColumnIDs=[3, 4])
        status, headers, _ = call(
            handler, "POST", "/import", req.SerializeToString(),
            content_type="application/x-protobuf",
            headers={"Accept": "application/x-protobuf"})
        assert status == 200
        assert stage_count("decode") == before_d + 1
        assert stage_count("apply") == before_a + 1
        stats = json.loads(headers["X-Pilosa-Stats"])
        assert stats["bits"] == 2
        assert stats["wireBytes"] > 0
        assert stats["decodeMs"] >= 0 and stats["applyMs"] >= 0


# -- SLO + health -------------------------------------------------------------

class TestSLOAndHealth:
    def test_burn_rate_from_histogram(self):
        reg = obs_metrics.Registry()
        hist = reg.histogram("pilosa_test_slo_seconds",
                             labels=("status",))
        tracker = SLOTracker(histogram=hist, objective_s=0.25,
                             target=0.9)
        # 10 fast, 10 slow → 50% bad; budget 10% → burn rate 5x.
        for _ in range(10):
            hist.labels("200").observe(0.01)
        for _ in range(10):
            hist.labels("200").observe(2.0)
        out = tracker.record()
        assert out["requestsTotal"] == 20
        assert out["goodTotal"] == 10
        assert out["burnRates"]["5m"] == pytest.approx(5.0)
        # All-good traffic decays the rolling burn toward zero.
        for _ in range(980):
            hist.labels("200").observe(0.01)
        out = tracker.record()
        assert out["burnRates"]["5m"] < 0.6

    def test_health_ready_and_unready(self, handler):
        status, _, body = call(handler, "GET", "/health")
        assert status == 200
        out = json.loads(body)
        assert out["status"] == "ok"
        assert set(out["checks"]) == {"holder", "gossip", "admission",
                                      "disk", "writeReady", "storage"}
        assert out["checks"]["storage"]["ok"] is True
        # A handler with no holder is NOT ready (and says why).
        bare = Handler(None, None)
        status, _, body = call(bare, "GET", "/health")
        assert status == 503
        out = json.loads(body)
        assert out["status"] == "unhealthy"
        assert out["checks"]["holder"]["ok"] is False

    def test_static_membership_stays_ready(self, holder):
        """Static/HTTP clusters have no failure detector —
        node_states() reports peers DOWN by construction, and /health
        must NOT let that drain a healthy cluster behind a load
        balancer."""
        from pilosa_tpu.cluster.topology import Cluster, Node
        cl = Cluster(nodes=[Node("a:1"), Node("b:2"), Node("c:3")])
        assert cl.node_set is None
        ready, checks = HealthChecker(holder=holder,
                                      cluster=cl).check()
        assert ready and checks["gossip"]["ok"]
        assert "static" in checks["gossip"]["detail"]

    def test_admission_saturation_unready(self, holder):
        from pilosa_tpu.sched import AdmissionController
        adm = AdmissionController(concurrency=1, queue_depth=1)
        checker = HealthChecker(holder=holder, admission=adm)
        ready, checks = checker.check()
        assert ready
        # Fill the slot AND the queue: the next arrival would be
        # rejected — the node must stop advertising ready.
        slot = adm.acquire("read")
        t = threading.Thread(target=lambda: adm.acquire("read").release(),
                             daemon=True)
        t.start()
        deadline = time.time() + 5
        while time.time() < deadline:
            snap = adm.snapshot()
            if sum((snap.get("queued") or {}).values()) >= 1:
                break
            time.sleep(0.01)
        ready, checks = checker.check()
        assert not ready and checks["admission"]["ok"] is False
        slot.release()
        t.join(timeout=5)

    def test_status_carries_slo_and_profiler(self, holder):
        from pilosa_tpu.obs.runtime import RuntimeCollector
        prof = ContinuousProfiler(hz=50, ring=64)
        tracker = SLOTracker()
        rc = RuntimeCollector(holder=holder, slo=tracker,
                              profiler=prof)
        snap = rc.collect()
        assert "burnRates" in snap["slo"]
        assert snap["profiler"]["running"] is False


# -- overhead guard -----------------------------------------------------------

class TestOverheadGuard:
    def test_accounting_and_profiler_under_5pct_cpu(self, handler,
                                                    holder):
        """Accounting ON + the continuous profiler at its default rate
        must cost <5% of the bench query leg's CPU. The query runs on
        the calling thread, so its cost is that thread's CPU time
        (``time.thread_time``): what the machine's other processes do
        to the wall clock is not in it. The profiler runs for the WHOLE
        measurement (its sampling load hits both modes; its per-query
        serving cost is zero by construction) and the accounting switch
        alternates in small interleaved groups, so whatever does move
        the CPU clock (cache, frequency) lands on both modes equally —
        the sums then differ only by the increments under test."""
        # A bench-leg-weight query (the suite's config-2 shape scaled
        # down): materializing Union over many rows — real container
        # algebra per query, so the fixed per-query ledger cost is
        # measured against realistic work, not an empty-frame no-op.
        frame = holder.create_index_if_not_exists("i") \
            .create_frame_if_not_exists("f")
        rng = np.random.default_rng(7)
        n_rows = 24
        for row in range(n_rows):
            cols = rng.choice(1 << 16, size=2000, replace=False)
            frame.import_bits(np.full(2000, row, np.uint64),
                              cols.astype(np.uint64))
        children = ", ".join(f"Bitmap(rowID={r}, frame=f)"
                             for r in range(n_rows))
        q = f"Union({children})".encode()

        def run_group(n=25) -> float:
            """CPU seconds of this thread over ``n`` queries."""
            cpu0 = time.thread_time()
            for _ in range(n):
                status, _, _ = call(handler, "POST", "/index/i/query",
                                    q)
                assert status == 200
            return time.thread_time() - cpu0

        prof = ContinuousProfiler()  # default rate
        run_group(50)  # warm caches/pools for both modes
        on_cpu = off_cpu = 0.0
        prof.start()
        try:
            for _ in range(12):
                accounting.set_enabled(False)
                off_cpu += run_group()
                accounting.set_enabled(True)
                on_cpu += run_group()
        finally:
            accounting.set_enabled(True)
            prof.stop()
        assert prof.samples_taken >= 1  # it really ran alongside
        assert off_cpu > 0.0
        ratio = on_cpu / off_cpu
        assert ratio < 1.05, (
            f"accounting+profiler overhead {ratio:.3f}x "
            f"(on {on_cpu * 1e3 / 300:.3f} ms a query"
            f" off {off_cpu * 1e3 / 300:.3f} ms of CPU)")
