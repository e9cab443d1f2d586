"""Cost-based planner + observability plane (ISSUE 18).

The load-bearing property is the differential one: for ANY read query,
planned execution must be bit-for-bit identical to unplanned — the
planner may only reorder, skip proven-empty work, serve cached
subresults, and re-place subtrees, never change an answer. Randomized
PQL trees run both ways on the host path and on the virtual device
mesh, with writes interleaved between queries so the generation-token
subresult keys must invalidate (a stale hit would show up as a wrong
bit). The observability half is contract-tested: fingerprint
normalization stability, ?plan=1 / ?profile=1 wire shapes, the
/debug/plans store, and the slow-log planFingerprint cross-link."""

import io
import json
import os

import numpy as np
import pytest

from pilosa_tpu import SLICE_WIDTH
from pilosa_tpu.executor import Executor
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.plan import record as plan_record
from pilosa_tpu.plan.planner import Planner, SubresultCache
from pilosa_tpu.plan.record import (PlanNode, PlanRecord,
                                    fingerprint_calls, normalize_call)
from pilosa_tpu.plan.store import PlanStore
from pilosa_tpu.pql import parser as pql

N_ROWS = 8
N_SLICES = 3


def _norm(results):
    out = []
    for r in results:
        if hasattr(r, "bits"):
            out.append(list(r.bits()))
        elif isinstance(r, list):
            out.append([(p.id, p.count) for p in r])
        else:
            out.append(r)
    return out


def _rand_tree(rng, depth, n_rows=N_ROWS):
    if depth == 0 or rng.random() < 0.4:
        # +2 headroom: absent rows are exactly the short-circuit food.
        return f"Bitmap(rowID={int(rng.integers(n_rows + 2))}, frame=f)"
    op = rng.choice(["Intersect", "Union", "Difference"])
    k = int(rng.integers(2, 5))
    return (f"{op}("
            + ", ".join(_rand_tree(rng, depth - 1, n_rows)
                        for _ in range(k)) + ")")


def _rand_query(rng):
    tree = _rand_tree(rng, int(rng.integers(1, 4)))
    wrap = rng.random()
    if wrap < 0.5:
        return f"Count({tree})"
    if wrap < 0.7:
        return f"TopN({tree}, frame=f, n=4)"
    return tree


# -- fingerprint contract ------------------------------------------------------


class TestFingerprint:
    def test_literals_normalize_away(self):
        a = pql.parse("Count(Bitmap(rowID=1, frame=f))").calls
        b = pql.parse("Count(Bitmap(rowID=999, frame=f))").calls
        assert fingerprint_calls(a) == fingerprint_calls(b)

    def test_commutative_operand_order_normalizes_away(self):
        a = pql.parse("Intersect(Bitmap(rowID=1, frame=f),"
                      " Bitmap(rowID=2, frame=g))").calls
        b = pql.parse("Intersect(Bitmap(rowID=7, frame=g),"
                      " Bitmap(rowID=3, frame=f))").calls
        assert fingerprint_calls(a) == fingerprint_calls(b)

    def test_difference_order_is_semantic(self):
        a = pql.parse("Difference(Bitmap(rowID=1, frame=f),"
                      " Bitmap(rowID=2, frame=g))").calls
        b = pql.parse("Difference(Bitmap(rowID=1, frame=g),"
                      " Bitmap(rowID=2, frame=f))").calls
        assert fingerprint_calls(a) != fingerprint_calls(b)

    def test_frame_names_distinguish(self):
        a = pql.parse("Count(Bitmap(rowID=1, frame=f))").calls
        b = pql.parse("Count(Bitmap(rowID=1, frame=g))").calls
        assert fingerprint_calls(a) != fingerprint_calls(b)

    def test_shape_distinguishes(self):
        a = pql.parse("Count(Bitmap(rowID=1, frame=f))").calls
        b = pql.parse("Count(Intersect(Bitmap(rowID=1, frame=f),"
                      " Bitmap(rowID=2, frame=f)))").calls
        assert fingerprint_calls(a) != fingerprint_calls(b)

    def test_normalize_call_masks_numbers_keeps_names(self):
        c = pql.parse("TopN(Bitmap(rowID=5, frame=f), frame=f,"
                      " n=10)").calls[0]
        text = normalize_call(c)
        assert "5" not in text and "10" not in text
        assert "f" in text and "TopN" in text


# -- plan record / wire shape --------------------------------------------------


class TestPlanRecord:
    def test_wire_json_roundtrips_and_stitches(self):
        rec = PlanRecord("abc123def456", node="n1")
        root = PlanNode("Count")
        root.est_rows = 10
        root.children.append(PlanNode("Bitmap", "f/1"))
        rec.roots.append(root)
        rec.note("reordered")
        leg = PlanRecord("abc123def456", node="n2")
        leg.roots.append(PlanNode("Count"))
        rec.add_remote_json(leg.wire_json())
        tree = rec.to_tree()
        assert tree["fingerprint"] == "abc123def456"
        assert tree["calls"][0]["op"] == "Count"
        assert tree["calls"][0]["children"][0]["detail"] == "f/1"
        assert tree["decisions"] == {"reordered": 1}
        assert tree["legs"][0]["node"] == "n2"
        # wire form parses back
        assert json.loads(rec.wire_json())["fingerprint"] == \
            "abc123def456"

    def test_wire_json_respects_budget(self):
        rec = PlanRecord("ff", node="n1")
        for i in range(40):
            n = PlanNode("Count", "x" * 200)
            rec.roots.append(n)
        payload = rec.wire_json(max_bytes=2000)
        assert len(payload) <= 2000
        assert json.loads(payload)["fingerprint"] == "ff"

    def test_remote_json_garbage_ignored(self):
        rec = PlanRecord("ff")
        rec.add_remote_json("{not json")
        rec.add_remote_json("[1,2]")
        assert rec.to_tree().get("legs") is None


class TestSubresultCache:
    def test_lru_entry_bound(self):
        c = SubresultCache(max_entries=4, max_bits=1 << 30)
        for i in range(8):
            c.put(("k", i), object(), 1)
        assert c.stats()["entries"] == 4
        assert c.get(("k", 0)) is None
        assert c.get(("k", 7)) is not None

    def test_bit_budget_bound(self):
        c = SubresultCache(max_entries=100, max_bits=10)
        c.put(("a",), object(), 6)
        c.put(("b",), object(), 6)  # 12 bits > 10: "a" evicts
        assert c.get(("a",)) is None
        assert c.get(("b",)) is not None

    def test_clear(self):
        c = SubresultCache()
        c.put(("a",), object(), 1)
        c.clear()
        assert c.stats() == {"entries": 0, "bits": 0}


class TestPlanStore:
    def test_aggregates_per_fingerprint(self):
        s = PlanStore()
        for i in range(5):
            s.record("fp1", {"op": "Count"}, 0.01 * (i + 1),
                     pql="Count(...)", est_rows=100, actual_rows=120)
        s.record("fp2", {"op": "TopN"}, 0.5)
        snap = s.snapshot()
        assert snap["fingerprints"] == 2
        top = snap["plans"][0]
        assert top["fingerprint"] == "fp1" and top["count"] == 5
        assert top["p50Ms"] > 0 and top["p99Ms"] >= top["p50Ms"]
        assert top["examplePql"] == "Count(...)"
        assert top["lastPlan"] == {"op": "Count"}
        assert abs(top["estActualDrift"]["median"] - 121 / 101) < 1e-3

    def test_fingerprint_lru_bound(self):
        s = PlanStore(max_fingerprints=3)
        for i in range(6):
            s.record(f"fp{i}", {}, 0.01)
        assert s.snapshot()["fingerprints"] == 3


# -- planner decisions ---------------------------------------------------------


@pytest.fixture
def planned_holder(tmp_path):
    holder = Holder(str(tmp_path / "data"))
    holder.open()
    idx = holder.create_index("p")
    f = idx.create_frame("f")
    rng = np.random.default_rng(7)
    # Skewed rows: row 0 huge, row counts decay; rows >= N_ROWS empty.
    for row in range(N_ROWS):
        k = max(4, 4000 >> row)
        cols = rng.choice(N_SLICES * SLICE_WIDTH, size=k,
                          replace=False)
        f.import_bits(np.full(k, row, dtype=np.uint64),
                      cols.astype(np.uint64))
    yield holder
    holder.close()


class TestPlannerDecisions:
    def test_reorders_intersect_smallest_first(self, planned_holder):
        ex = Executor(planned_holder, host="local", use_mesh=False)
        tree = ex.explain(
            "p", "Count(Intersect(Bitmap(rowID=0, frame=f),"
                 " Bitmap(rowID=5, frame=f)))")
        node = tree["calls"][0]["children"][0]
        assert "reordered" in node.get("decisions", [])
        ests = [c["estRows"] for c in node["children"]]
        assert ests == sorted(ests)

    def test_short_circuits_empty_intersect(self, planned_holder):
        ex = Executor(planned_holder, host="local", use_mesh=False)
        tree = ex.explain(
            "p", f"Count(Intersect(Bitmap(rowID=0, frame=f),"
                 f" Bitmap(rowID={N_ROWS + 1}, frame=f)))")
        root = tree["calls"][0]
        assert root["estRows"] == 0 and root["exact"]
        assert "short_circuit" in root["decisions"]

    def test_estimates_are_exact_on_local_slices(self, planned_holder):
        ex = Executor(planned_holder, host="local", use_mesh=False)
        tree = ex.explain("p", "Bitmap(rowID=3, frame=f)")
        leaf = tree["calls"][0]
        want = ex.execute("p", "Count(Bitmap(rowID=3, frame=f))")[0]
        assert leaf["estRows"] == want and leaf["exact"]

    def test_explain_does_not_execute(self, planned_holder):
        ex = Executor(planned_holder, host="local", use_mesh=False)
        tree = ex.explain("p", "Count(Bitmap(rowID=0, frame=f))")
        assert tree["calls"][0]["op"] == "Count"
        assert "actualS" not in tree["calls"][0]
        with pytest.raises(Exception):
            ex.explain("p", "SetBit(frame=f, rowID=1, columnID=2)")

    def test_subresult_cache_hits_across_queries(self, planned_holder):
        ex = Executor(planned_holder, host="local", use_mesh=False)
        q = ("Count(Union(Bitmap(rowID=1, frame=f),"
             " Bitmap(rowID=2, frame=f)))")
        want = ex.execute("p", q)[0]
        before = ex.planner.subresults.stats()["entries"]
        for _ in range(3):
            ex._bitmap_results.clear()  # force past whole-result cache
            assert ex.execute("p", q)[0] == want
        assert ex.planner.subresults.stats()["entries"] > before

    def test_disabled_planner_attaches_nothing(self, planned_holder):
        ex = Executor(planned_holder, host="local", use_mesh=False)
        ex.planner_enabled = False
        from pilosa_tpu.executor import ExecOptions
        from pilosa_tpu.sched.context import QueryContext
        ctx = QueryContext(pql="x", index="p")
        ex.execute("p", "Count(Bitmap(rowID=0, frame=f))",
                   opt=ExecOptions(ctx=ctx))
        assert ctx.plan is None


# -- shape entries: reuse across row ids, validity, sampling -------------------


class TestPlanMemo:
    def test_hit_reuses_the_shape_entry_whatever_the_row(
            self, planned_holder):
        ex = Executor(planned_holder, host="local", use_mesh=False)
        unplanned = Executor(planned_holder, host="local",
                             use_mesh=False)
        unplanned.planner_enabled = False

        def q(row):
            return f"Count(Bitmap(rowID={row}, frame=f))"

        def plans():  # the memo also holds the index's route record
            return [e for k, e in ex.planner._plans.items()
                    if k[0] != "route"]
        assert ex.execute("p", q(1)) == unplanned.execute("p", q(1))
        assert len(plans()) == 1
        ent = plans()[0]
        assert ent["hits"] == 0
        assert ex.planner.shapes == {"hits": 0, "misses": 1, "full": 0}
        for row in (2, 3, N_ROWS + 1):   # other rows, the same shape
            ex._bitmap_results.clear()
            assert ex.execute("p", q(row)) == \
                unplanned.execute("p", q(row))
        assert plans() == [ent]
        assert ent["hits"] == 3
        assert ex.planner.shapes == {"hits": 3, "misses": 1, "full": 0}
        assert ex.planner.decision_totals["planned"] == 1

    def test_write_invalidates_memoized_plan(self, planned_holder):
        ex = Executor(planned_holder, host="local", use_mesh=False)
        q = "Count(Bitmap(rowID=1, frame=f))"
        before = ex.execute("p", q)[0]
        ex._bitmap_results.clear()
        ex.execute("p", q)  # memoized now
        free_col = N_SLICES * SLICE_WIDTH - 1
        ex.execute("p", f"SetBit(frame=f, rowID=1, columnID={free_col})")
        ex._bitmap_results.clear()
        assert ex.execute("p", q)[0] == before + 1

    def test_view_appearing_voids_short_circuit_proof(self,
                                                      planned_holder):
        # An empty frame's missing standard view is an exact-0 proof;
        # the first write creates the view and MUST void the shape
        # entry that resolved "no view", or it would keep answering 0.
        planned_holder.index("p").create_frame("g")
        ex = Executor(planned_holder, host="local", use_mesh=False)
        bits = list(ex.execute("p", "Bitmap(rowID=0, frame=f)")[0].bits())
        col = bits[0]
        q = (f"Count(Intersect(Bitmap(rowID=0, frame=f),"
             f" Bitmap(rowID=0, frame=g)))")
        for _ in range(2):  # second run binds the kept shape
            ex._bitmap_results.clear()
            assert ex.execute("p", q)[0] == 0
        ex.execute("p", f"SetBit(frame=g, rowID=0, columnID={col})")
        ex._bitmap_results.clear()
        assert ex.execute("p", q)[0] == 1

    def test_memo_is_lru_bounded(self, planned_holder):
        from pilosa_tpu.plan.planner import _PLAN_MEMO_ENTRIES
        ex = Executor(planned_holder, host="local", use_mesh=False)
        for i in range(_PLAN_MEMO_ENTRIES + 20):
            ex.execute("p", f"Count(Bitmap(rowID={i}, frame=f))")
        # every row id of one shape is ONE entry (+ the route record)
        assert len(ex.planner._plans) == 2
        slices = list(range(N_SLICES))
        for i in range(_PLAN_MEMO_ENTRIES + 20):   # as many shapes
            calls = pql.parse(
                f"Count(Bitmap(rowID=1, frame=f{i}))").calls
            ex.planner.plan_query_cached("p", calls, slices)
        assert len(ex.planner._plans) == _PLAN_MEMO_ENTRIES

    def test_first_sighting_samples_and_hits_sample_1_in_16(
            self, planned_holder):
        from pilosa_tpu.executor import ExecOptions
        ex = Executor(planned_holder, host="local", use_mesh=False)
        slices = list(range(N_SLICES))

        def plan(row):
            query = pql.parse(f"Count(Bitmap(rowID={row}, frame=f))")
            return ex._maybe_plan("p", query, slices, ExecOptions())[1]
        assert plan(2).sample  # the shape's first sighting: in full
        samples = [plan(row % N_ROWS).sample for row in range(16)]
        assert samples.count(True) == 1 and samples[-1]


# -- randomized differential: planned == unplanned (host) ----------------------


class _CountingLock:
    """``threading.Lock`` that counts its acquisitions."""

    def __init__(self):
        import threading
        self._mu = threading.Lock()
        self.taken = 0

    def __enter__(self):
        self._mu.acquire()
        self.taken += 1
        return self

    def __exit__(self, *exc):
        self._mu.release()
        return False


class TestPlannerLock:
    @pytest.mark.parametrize("k", [2, 3, 4])
    def test_a_warm_shape_takes_the_lock_twice_whatever_the_rows(
            self, tmp_path, k):
        """Planning a k-leaf Count over many slices samples 8 slices a
        leaf; each sample used to take the planner's lock (16-32 times
        a request), and eight connection threads convoyed on it: the
        served ``plan`` stage read 0.2 ms or 8-20 ms for tens of
        seconds at a time (PERF.md, PR 27). A warm shape takes it for
        the route record's get and for the CSE ladder — whatever the
        leaf and slice counts and whichever rows are named (its entry
        is read without the lock; one hit in 16 renews its LRU place
        under it)."""
        from pilosa_tpu.plan import planner as planner_mod
        holder = Holder(str(tmp_path / "d"))
        holder.open()
        try:
            f = holder.create_index("w").create_frame("f")
            n_slices = planner_mod.EXACT_SLICES + 8     # sampled, not exact
            for row in range(6):
                cols = (np.arange(n_slices, dtype=np.uint64)
                        * SLICE_WIDTH + row)
                f.import_bits(np.full(n_slices, row, dtype=np.uint64),
                              cols)
            ex = Executor(holder, host="local", use_mesh=False)

            def q(rows):
                return "Count(Intersect(%s))" % ", ".join(
                    f"Bitmap(rowID={r}, frame=f)" for r in rows)
            for first in range(6 - k + 1):      # warms every estimate
                assert ex.execute("w", q(range(first, first + k)))[0] \
                    == 0
            lock = ex.planner._mu = _CountingLock()
            hits = ex.planner.shapes["hits"]
            rows = list(range(k))[::-1]         # rows not asked together yet
            assert ex.execute("w", q(rows))[0] == 0
            assert 0 < lock.taken <= 2, lock.taken
            assert ex.planner.shapes["hits"] == hits + 1
            assert ex.planner.decision_totals["planned"] == 1
        finally:
            holder.close()


class TestPlannedVsUnplannedDifferential:
    @pytest.mark.parametrize("seed", [11, 12, 13])
    def test_random_trees_with_writes_between(self, tmp_path, seed):
        """The acceptance leg: random PQL trees, planned and unplanned
        executors over the SAME holder, bit-for-bit equality — with
        writes interleaved so every cached subresult's generation
        token must invalidate (a stale hit diverges the executors)."""
        rng = np.random.default_rng(seed)
        holder = Holder(str(tmp_path / "d"))
        holder.open()
        try:
            idx = holder.create_index("q")
            f = idx.create_frame("f")
            n_cols = N_SLICES * SLICE_WIDTH
            for row in range(N_ROWS):
                k = max(2, 2000 >> row)
                cols = rng.choice(n_cols, size=k, replace=False)
                f.import_bits(np.full(k, row, dtype=np.uint64),
                              cols.astype(np.uint64))
            planned = Executor(holder, host="local", use_mesh=False)
            unplanned = Executor(holder, host="local", use_mesh=False)
            unplanned.planner_enabled = False
            for step in range(60):
                if rng.random() < 0.3:
                    # Write between queries: the token-keyed
                    # invalidation leg. Writes go through the PLANNED
                    # executor (they bypass planning by contract).
                    r = int(rng.integers(N_ROWS))
                    c = int(rng.integers(n_cols))
                    verb = ("SetBit" if rng.random() < 0.7
                            else "ClearBit")
                    planned.execute(
                        "q", f"{verb}(frame=f, rowID={r},"
                             f" columnID={c})")
                    continue
                q = _rand_query(rng)
                got = _norm(planned.execute("q", q))
                want = _norm(unplanned.execute("q", q))
                assert got == want, (seed, step, q)
            # The run must actually have exercised the machinery.
            totals = planned.planner.decision_totals
            assert totals.get("planned", 0) > 0
        finally:
            holder.close()

    def test_repeated_query_after_write_is_fresh(self, tmp_path):
        """Directed token-invalidation check: prime the subresult
        cache hard (same interior subtree many times), then write one
        bit inside it — the next answer must include the new bit."""
        holder = Holder(str(tmp_path / "d"))
        holder.open()
        try:
            idx = holder.create_index("q")
            f = idx.create_frame("f")
            f.import_bits(np.zeros(50, dtype=np.uint64),
                          np.arange(50, dtype=np.uint64))
            f.import_bits(np.ones(50, dtype=np.uint64),
                          np.arange(25, 75, dtype=np.uint64))
            ex = Executor(holder, host="local", use_mesh=False)
            q = ("Count(Union(Bitmap(rowID=0, frame=f),"
                 " Bitmap(rowID=1, frame=f)))")
            for _ in range(4):
                ex._bitmap_results.clear()
                assert ex.execute("q", q)[0] == 75
            ex.execute("q", "SetBit(frame=f, rowID=0, columnID=1000)")
            ex._bitmap_results.clear()
            assert ex.execute("q", q)[0] == 76
        finally:
            holder.close()


# -- randomized differential: device leg ---------------------------------------


class TestPlannedDeviceDifferential:
    @pytest.mark.parametrize("seed", [21, 22])
    def test_planned_device_matches_unplanned_host(self, tmp_path,
                                                   seed):
        """Planned execution on the virtual device mesh vs unplanned
        host execution: the placement hints and short-circuits must
        compose with the device lowering without changing a bit."""
        rng = np.random.default_rng(seed)
        holder = Holder(str(tmp_path / "d"))
        holder.open()
        try:
            idx = holder.create_index("q")
            f = idx.create_frame("f")
            n_cols = N_SLICES * SLICE_WIDTH
            for row in range(N_ROWS):
                k = max(8, 3000 >> row)
                cols = rng.choice(n_cols, size=k, replace=False)
                f.import_bits(np.full(k, row, dtype=np.uint64),
                              cols.astype(np.uint64))
            device = Executor(holder, host="local", use_mesh=True,
                              mesh_min_slices=1)
            host = Executor(holder, host="local", use_mesh=False)
            host.planner_enabled = False
            for step in range(15):
                q = f"Count({_rand_tree(rng, 2)})"
                got = device.execute("q", q)
                want = host.execute("q", q)
                assert got == want, (seed, step, q)
            device.close()
            host.close()
        finally:
            holder.close()


# -- the serving surface -------------------------------------------------------


def _call(app, method, path, body=b""):
    if "?" in path:
        path, _, qs = path.partition("?")
    else:
        qs = ""
    environ = {"REQUEST_METHOD": method, "PATH_INFO": path,
               "QUERY_STRING": qs,
               "CONTENT_LENGTH": str(len(body)),
               "wsgi.input": io.BytesIO(body)}
    out = {}

    def start_response(status, headers):
        out["status"] = int(status.split()[0])
        out["headers"] = dict(headers)

    chunks = app(environ, start_response)
    return out["status"], out["headers"], b"".join(chunks)


@pytest.fixture
def served(planned_holder):
    from pilosa_tpu.sched import QueryRegistry
    from pilosa_tpu.server.handler import Handler
    ex = Executor(planned_holder, host="local", use_mesh=False)
    registry = QueryRegistry(slow_threshold_s=1e-9)
    h = Handler(planned_holder, ex, host="local", registry=registry)
    yield h, ex, registry


class TestServingSurface:
    def test_plan_flag_returns_explain_only(self, served):
        h, ex, _reg = served
        st, _hd, body = _call(
            h, "POST", "/index/p/query?plan=1",
            b"Count(Bitmap(rowID=0, frame=f))")
        assert st == 200
        doc = json.loads(body)
        assert doc["results"] == []
        assert doc["plan"]["calls"][0]["op"] == "Count"
        assert "actualS" not in doc["plan"]["calls"][0]
        # EXPLAIN of a write is a 400, and nothing executed either way.
        st, _hd, body = _call(
            h, "POST", "/index/p/query?plan=1",
            b"SetBit(frame=f, rowID=0, columnID=99999999)")
        assert st == 400

    def test_profile_embeds_analyzed_plan(self, served):
        h, _ex, _reg = served
        st, _hd, body = _call(
            h, "POST", "/index/p/query?profile=1",
            b"Count(Intersect(Bitmap(rowID=0, frame=f),"
            b" Bitmap(rowID=1, frame=f)))")
        assert st == 200
        doc = json.loads(body)
        plan = doc["plan"]
        assert plan["fingerprint"]
        root = plan["calls"][0]
        assert root["op"] == "Count"
        assert "actualS" in root        # ANALYZE: wall time recorded
        assert root["actualRows"] == doc["results"][0]

    def test_debug_plans_aggregates(self, served):
        h, _ex, _reg = served
        # Same shape, different literals: the first sighting records
        # in full, then one request in 16 (the reservoir).
        for row in range(33):
            _call(h, "POST", "/index/p/query",
                  f"Count(Bitmap(rowID={row % N_ROWS}, frame=f))".encode())
        st, _hd, body = _call(h, "GET", "/debug/plans")
        assert st == 200
        doc = json.loads(body)
        assert doc["enabled"] is True
        assert doc["fingerprints"] >= 1
        top = doc["plans"][0]
        assert top["count"] == 3     # 33 requests, ONE fingerprint
        assert top["lastPlan"]["calls"][0]["op"] == "Count"
        assert doc["planner"]["decisions"].get("planned", 0) == 1
        assert doc["planner"]["shapes"] == {"hits": 32, "misses": 1,
                                            "full": 0}

    def test_slow_log_cross_links_fingerprint(self, served):
        h, _ex, reg = served
        _call(h, "POST", "/index/p/query",
              b"Count(Bitmap(rowID=0, frame=f))")
        slow = reg.slow_queries()
        assert slow, "threshold 1e-9 must catch every query"
        entry = slow[-1]
        assert entry["planFingerprint"]
        st, _hd, body = _call(h, "GET", "/debug/plans")
        fps = [p["fingerprint"]
               for p in json.loads(body)["plans"]]
        assert entry["planFingerprint"] in fps

    def test_planner_off_still_serves(self, served):
        h, ex, _reg = served
        ex.planner_enabled = False
        st, _hd, body = _call(h, "POST", "/index/p/query",
                              b"Count(Bitmap(rowID=0, frame=f))")
        assert st == 200
        doc = json.loads(body)
        assert isinstance(doc["results"][0], int)
        st, _hd, body = _call(h, "POST", "/index/p/query?profile=1",
                              b"Count(Bitmap(rowID=0, frame=f))")
        assert "plan" not in json.loads(body)

    def test_plan_disabled_globally(self, served):
        h, _ex, _reg = served
        plan_record.set_enabled(False)
        try:
            st, _hd, body = _call(h, "POST", "/index/p/query",
                                  b"Count(Bitmap(rowID=0, frame=f))")
            assert st == 200
        finally:
            plan_record.set_enabled(True)


# -- real 2-node cluster: stitched plans + differential ------------------------


def test_two_node_cluster_plans_stitch_and_match_model(tmp_path):
    """Spawn a REAL 2-node gossip cluster with replicas=1 so slices
    split across nodes and every fan-out query has a genuine remote
    leg. Asserts (a) planned answers stay model-exact over the wire,
    including after writes (cluster-wide token invalidation), and
    (b) ?profile=1 returns ONE plan tree with the remote node's leg
    stitched in via the X-Pilosa-Plan header."""
    import signal
    import subprocess
    import sys as _sys
    import urllib.request

    _here = os.path.dirname(os.path.abspath(__file__))
    _sys.path.insert(0, _here)
    from podenv import cpu_env, free_port, wait_up

    def post(host, path, body):
        req = urllib.request.Request(f"http://{host}{path}",
                                     data=body, method="POST")
        return urllib.request.urlopen(req, timeout=30).read()

    def query(host, body, extra=""):
        return json.loads(post(host, f"/index/cp/query{extra}",
                               body.encode()))

    pa, pb = free_port(), free_port()
    ga, gb = free_port(), free_port()
    hosts = f"127.0.0.1:{pa},127.0.0.1:{pb}"
    procs, logs = [], []

    def spawn(name, port, internal, seed=""):
        d = tmp_path / name
        d.mkdir(exist_ok=True)
        env = cpu_env()
        env["PILOSA_TPU_MESH"] = "0"
        env["PILOSA_TPU_WARMUP"] = "0"
        log = open(tmp_path / f"{name}.log", "a")
        logs.append(log)
        argv = [_sys.executable, "-m", "pilosa_tpu.cli", "server",
                "-d", str(d), "-b", f"127.0.0.1:{port}",
                "--cluster.type", "gossip",
                "--cluster.hosts", hosts,
                "--cluster.replicas", "1",
                "--cluster.internal-port", str(internal),
                "--anti-entropy.interval", "300s"]
        if seed:
            argv += ["--cluster.gossip-seed", seed]
        p = subprocess.Popen(argv, env=env, stdout=log, stderr=log,
                             cwd=os.path.dirname(_here))
        procs.append(p)
        wait_up(f"127.0.0.1:{port}")
        return f"127.0.0.1:{port}"

    try:
        host_a = spawn("a", pa, ga)
        host_b = spawn("b", pb, gb, seed=f"127.0.0.1:{ga}")
        post(host_a, "/index/cp", b"{}")
        post(host_a, "/index/cp/frame/f", b"{}")

        rng = np.random.default_rng(42)
        bits: dict[int, set[int]] = {}
        n_rows, n_cols = 10, 3 * SLICE_WIDTH

        # Seed every slice so ownership splits matter from query one.
        from pilosa_tpu.cluster.client import Client
        client = Client(host_a)
        k = 1500
        rows = rng.integers(0, n_rows, k).astype(np.uint64)
        cols = rng.integers(0, n_cols, k).astype(np.uint64)
        client.import_arrays("cp", "f", rows, cols)
        for r, c in zip(rows.tolist(), cols.tolist()):
            bits.setdefault(r, set()).add(c)

        # The CreateSlice broadcast is async: wait until BOTH nodes
        # know the cluster-wide max slice, or queries routed through
        # the node that did not take the import see a partial range.
        import time as _time
        deadline = _time.monotonic() + 30
        while _time.monotonic() < deadline:
            ms = [json.loads(urllib.request.urlopen(
                      f"http://{n}/slices/max", timeout=30).read())
                  ["maxSlices"].get("cp") for n in (host_a, host_b)]
            if ms == [2, 2]:
                break
            _time.sleep(0.2)
        else:
            raise AssertionError(f"max-slice never converged: {ms}")

        def check(node, q, want):
            assert query(node, q)["results"][0] == want, q

        for step in range(30):
            node = (host_a, host_b)[int(rng.integers(0, 2))]
            kind = int(rng.integers(0, 4))
            if kind == 0:  # write between queries: invalidation leg
                r = int(rng.integers(0, n_rows))
                c = int(rng.integers(0, n_cols))
                query(node, f"SetBit(frame=f, rowID={r},"
                            f" columnID={c})")
                bits.setdefault(r, set()).add(c)
            elif kind == 1:
                a, b = rng.integers(0, n_rows, 2).tolist()
                check(node,
                      f"Count(Intersect(Bitmap(rowID={a}, frame=f),"
                      f" Bitmap(rowID={b}, frame=f)))",
                      len(bits.get(a, set()) & bits.get(b, set())))
            elif kind == 2:
                ids = rng.integers(0, n_rows, 3).tolist()
                want = len(set().union(
                    *(bits.get(r, set()) for r in ids)))
                check(node, "Count(Union(" + ", ".join(
                    f"Bitmap(rowID={r}, frame=f)"
                    for r in ids) + "))", want)
            else:  # empty-row short-circuit still exact over the wire
                a = int(rng.integers(0, n_rows))
                check(node,
                      f"Count(Intersect(Bitmap(rowID={a}, frame=f),"
                      f" Bitmap(rowID={n_rows + 3}, frame=f)))", 0)

        # The observability acceptance check: one profiled query,
        # one plan tree, remote leg(s) stitched under "legs".
        doc = query(host_a,
                    "Count(Union(Bitmap(rowID=0, frame=f),"
                    " Bitmap(rowID=1, frame=f)))", "?profile=1")
        want = len(bits.get(0, set()) | bits.get(1, set()))
        assert doc["results"][0] == want
        plan = doc.get("plan")
        assert plan is not None and plan["fingerprint"]
        assert plan["calls"][0]["op"] == "Count"
        legs = plan.get("legs") or []
        assert legs, "replicas=1 over 3 slices must produce a remote leg"
        assert all(leg["fingerprint"] == plan["fingerprint"]
                   for leg in legs)
        assert any(leg.get("calls") for leg in legs)

        # Both nodes' /debug/plans carry the fingerprint store.
        for node in (host_a, host_b):
            with urllib.request.urlopen(
                    f"http://{node}/debug/plans", timeout=30) as resp:
                dbg = json.loads(resp.read())
            assert dbg["enabled"] is True
            assert dbg["fingerprints"] >= 1
    finally:
        for p in procs:
            try:
                p.send_signal(signal.SIGINT)
            except OSError:
                pass
        for p in procs:
            try:
                p.wait(timeout=20)
            except subprocess.TimeoutExpired:
                p.kill()
        for log in logs:
            log.close()
