"""The route record (ISSUE 26): a whole-index read's route — slice list,
ownership, slice→node groups, each leaf view's fragment list — resolved
once and reused until a token says it may have changed.

The per-slice walk (``_slices_by_node``, ``_owns_all_slices``,
``_leaf_frags``) stays in the code as the slow path; here it is the
oracle, and what a leaf slab holds is the row's bits by that walk. The memo must be
ENGAGED wherever a test says so: every such test reads
``Executor.route_memo`` and finds hits."""

import sys
import threading
import time
import types

import numpy as np
import pytest

from pilosa_tpu import SLICE_WIDTH
from pilosa_tpu.cluster import topology
from pilosa_tpu.cluster.topology import Node, new_cluster
from pilosa_tpu.executor import ExecOptions, Executor
from pilosa_tpu.fault import FaultManager
from pilosa_tpu.models.frame import Field, FrameOptions
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.models.view import VIEW_STANDARD, View
from pilosa_tpu.parallel import residency

ROWS = 4
Q3 = ("Count(Intersect(Bitmap(frame=f, rowID=0), Bitmap(frame=f, rowID=1),"
      " Bitmap(frame=f, rowID=2)))")
QV = "Count(Range(frame=f, v > 10))"


def _load(holder, n_slices, index="i", seed=7):
    """``n_slices`` slices x ROWS rows, a few bits each, plus a BSI
    field ``v`` with a value in every slice."""
    idx = holder.create_index_if_not_exists(index)
    fr = idx.create_frame_if_not_exists("f", FrameOptions())
    fr.create_field(Field("v", 0, 100))
    rng = np.random.default_rng(seed)
    rows, cols = [], []
    for s in range(n_slices):
        for r in range(ROWS):
            c = rng.integers(0, 16, size=6)
            rows += [r] * len(c)
            cols += (s * SLICE_WIDTH + c).tolist()
    fr.import_bits(np.array(rows, dtype=np.uint64),
                   np.array(cols, dtype=np.uint64))
    vcols = np.arange(n_slices, dtype=np.uint64) * SLICE_WIDTH + 3
    fr.import_field_values("v", vcols, (vcols // SLICE_WIDTH) % 50 + 1)
    return idx, fr


class _PeerHolder:
    """The same data as seen by a node whose own copies are healthy:
    the test's quarantine and tier block are the LOCAL node's."""
    quarantine = None
    tier = None

    def __init__(self, holder):
        self._h = holder

    def __getattr__(self, name):
        return getattr(self._h, name)


class _PeerClient:
    """Remote legs run in-process: one host-path executor a peer over
    the shared holder (the reference's mock-executor seam)."""

    def __init__(self, holder, cluster):
        self.holder, self.cluster = _PeerHolder(holder), cluster
        self.peers: dict[str, Executor] = {}
        self.calls = 0

    def execute_query(self, node, index, query, slices, remote):
        self.calls += 1
        ex = self.peers.get(node.host)
        if ex is None:
            ex = self.peers[node.host] = Executor(
                self.holder, host=node.host, cluster=self.cluster,
                use_mesh=False)
        return ex.execute(index, query, slices,
                          ExecOptions(remote=True))


def _oracle(holder, q, index="i"):
    """The exact answer by the per-slice host path, one node."""
    ex = Executor(_PeerHolder(holder), host="local", use_mesh=False)
    ex.planner_enabled = False
    return ex.execute(index, q)[0]


def _walk_key(ex, index, leaf, slices):
    """What a leaf slab over ``slices`` holds, by the walk: the row's
    bits in each fragment of the set. Two lookups may share a slab only
    where these are equal."""
    frame, view, row = leaf
    frags = [ex.holder.fragment(index, frame, view, s) for s in slices]
    return (index, frame, view, row, tuple(slices),
            tuple(tuple(f.row(row, check_cache=False).bits())
                  if f is not None else () for f in frags))


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "data"))
    h.open()
    yield h
    h.close()


@pytest.fixture(autouse=True)
def _fresh_residency():
    residency.device_cache().clear()
    yield
    residency.device_cache().clear()


# -- differential: the record against the walk -------------------------------


@pytest.mark.parametrize("n_slices", [1, 8, 256])
class TestRecordEqualsWalk:
    def _warm(self, holder, n_slices):
        _load(holder, n_slices)
        ex = Executor(holder, host="local", use_mesh=True,
                      mesh_min_slices=1)
        want = _oracle(holder, Q3)
        for _ in range(3):
            assert ex.execute("i", Q3)[0] == want
        assert ex.route_memo["hits"] == 2       # the memo is engaged
        assert ex.route_memo["misses"] == 1
        route = ex.planner.memo_get(("route", "i"))
        assert route is not None
        return ex, route

    def test_slices_groups_ownership(self, holder, n_slices):
        ex, route = self._warm(holder, n_slices)
        plain = list(range(n_slices))
        assert list(route["slices"]) == plain
        assert route["slices"].route is route
        walked = ex._slices_by_node(list(ex.cluster.nodes), "i", plain)
        assert [(n, list(g)) for n, g in route["groups"]] == walked
        assert all(a[0] is b[0]
                   for a, b in zip(route["groups"], walked))
        assert route["all_local"] is ex._owns_all_slices("i", plain)
        # the whole-list group IS the record's list: the leg finds it
        assert route["groups"][0][1] is route["slices"]

    def test_fragment_lists(self, holder, n_slices):
        ex, route = self._warm(holder, n_slices)
        assert ex.execute("i", QV)[0] == _oracle(holder, QV)
        assert set(route["views"]) == {("f", VIEW_STANDARD),
                                       ("f", "field_v")}
        plain = list(range(n_slices))
        for (frame, view), (tok, frags) in route["views"].items():
            walked = ex._leaf_frags("i", frame, view, plain)
            assert len(frags) == n_slices
            assert all(a is b for a, b in zip(frags, walked))
            v = holder.view("i", frame, view)
            assert tok == (v.uid, v.generation)
            mesh = ex._mesh_or_none()
            # a leaf slab is keyed on the view's uid and its row's token
            key = ex._leaf_cache_key(mesh, "i", (frame, view, 0),
                                     route["slices"])
            assert key[-4:-1] == (v.uid, *v.row_token(0))
            # a candidate block on the view's token, and its fragments
            # are the record's
            if view == VIEW_STANDARD:
                rows_key = ex._topn_rows_key(mesh, "i", frame, (0, 1),
                                             route["slices"])
                assert rows_key[-3:-1] == tok
                assert ex._key_frags("i", frame, view, route["slices"],
                                     rows_key)() is frags

    def test_key_equality_classes(self, holder, n_slices):
        """Two lookups share a slab only where the row's bits in the set
        are the same: however the same slice set arrives it is one
        class; another set, another row, or a write to the row between
        them is another class — and a write to another row is not."""
        ex, route = self._warm(holder, n_slices)
        mesh = ex._mesh_or_none()
        leaf, other = ("f", VIEW_STANDARD, 0), ("f", VIEW_STANDARD, 3)
        plain = list(range(n_slices))
        forms = {"routed": route["slices"], "plain": plain,
                 "tuple": tuple(plain)}
        if n_slices > 1:
            forms["head"] = plain[:-1]
            forms["tail"] = plain[1:]
        if n_slices > 2:
            forms["holed"] = plain[:1] + plain[2:]
            forms["reversed"] = plain[::-1]
        seen = []   # (new key, walk key) of every lookup, over time

        def look():
            for lf in (leaf, other):
                for sl in forms.values():
                    seen.append((ex._leaf_cache_key(mesh, "i", lf, sl),
                                 _walk_key(ex, "i", lf, sl)))
        look()
        ex.execute("i", f"SetBit(frame=f, rowID=3, columnID="
                        f"{(n_slices - 1) * SLICE_WIDTH + 77})")
        look()
        ex.execute("i", "ClearBit(frame=f, rowID=3, columnID=77)")
        look()
        for i, (new_a, old_a) in enumerate(seen):
            for new_b, old_b in seen[i + 1:]:
                if old_a != old_b:      # the walk says: not the same slab
                    assert new_a != new_b
                elif new_a != new_b and new_a[:7] == new_b[:7]:
                    # Coarser only for the row that was written: where a
                    # write to it fell OUTSIDE the slice set (its token
                    # is per view), or a write undid one; never for an
                    # unwritten row.
                    assert old_a[3] == 3
        # the row nobody wrote kept its key, in every form, throughout
        per_look = len(seen) // 3
        assert [k for k, _ in seen[:per_look] if k[5] == 0] \
            == [k for k, _ in seen[per_look:2 * per_look] if k[5] == 0] \
            == [k for k, _ in seen[2 * per_look:] if k[5] == 0]
        # one class for the whole index, whatever the form
        now = {ex._leaf_cache_key(mesh, "i", leaf, sl)
               for sl in (route["slices"], plain, tuple(plain))}
        assert len(now) == 1
        hash(now.pop())     # O(1): ("r", first, n), no slice tuple
        assert ex._slices_key(route["slices"]) == ("r", 0, n_slices)


def test_keys_do_not_iterate_fragments(holder, monkeypatch):
    _load(holder, 64)
    ex = Executor(holder, host="local", use_mesh=True, mesh_min_slices=1)
    ex.execute("i", Q3)
    mesh = ex._mesh_or_none()
    route = ex.planner.memo_get(("route", "i"))
    calls = []
    monkeypatch.setattr(View, "fragment",
                        lambda self, s: calls.append(s))
    for sl in (route["slices"], list(range(64))):
        ex._leaf_cache_key(mesh, "i", ("f", VIEW_STANDARD, 0), sl)
        ex._topn_rows_key(mesh, "i", "f", (0, 1, 2), sl)
    assert calls == []


# -- invalidation matrix -----------------------------------------------------

N = 8


def _setbit(ex, h):
    col = 3 * SLICE_WIDTH + 900
    for r in range(3):
        ex.execute("i", f"SetBit(frame=f, rowID={r}, columnID={col})")


def _clearbit(ex, h):
    common = None
    for r in range(3):
        bits = set(ex.execute("i", f"Bitmap(frame=f, rowID={r})")[0].bits())
        common = bits if common is None else common & bits
    assert common, "fixture: rows 0-2 share no column"
    ex.execute("i", f"ClearBit(frame=f, rowID=1, columnID={min(common)})")


def _bulk_import(ex, h):
    cols = np.arange(N, dtype=np.uint64) * SLICE_WIDTH + 555
    for r in range(3):
        h.frame("i", "f").import_bits(
            np.full(N, r, dtype=np.uint64), cols)


def _set_field_value(ex, h):
    ex.execute("i", f"SetFieldValue(frame=f, columnID="
                    f"{5 * SLICE_WIDTH + 3}, v=99)")
    ex.execute("i", f"SetFieldValue(frame=f, columnID="
                    f"{5 * SLICE_WIDTH + 4}, v=5)")


def _snapshot(ex, h):
    h.fragment("i", "f", VIEW_STANDARD, 2).snapshot()


def _close_reopen(ex, h):
    frag = h.fragment("i", "f", VIEW_STANDARD, 4)
    frag.close()
    frag.open()


def _new_slice(ex, h):
    col = N * SLICE_WIDTH + 1
    for r in range(3):
        ex.execute("i", f"SetBit(frame=f, rowID={r}, columnID={col})")


def _frame_recreate(ex, h):
    h.index("i").delete_frame("f")
    _load(h, N, seed=11)


def _node_added(ex, h):
    ex.cluster.nodes.append(Node("third"))


def _resize_begun(ex, h):
    ex.cluster.install_resize("r1", ["local", "peer", "new"])


def _quarantined(ex, h):
    h.quarantine.add(h.fragment("i", "f", VIEW_STANDARD, 1), "test")


def _tier_block(ex, h):
    blocked = {("i", 6): 1}
    h.tier = types.SimpleNamespace(
        _blocked_slices=blocked,
        slice_blocked=lambda index, s: (index, s) in blocked)


def _open_circuit(ex, h):
    ex.fault.breakers.force_open("peer", reason="test")


# (event, the query that must see it, memo re-engages after the drop)
MATRIX = [
    (_setbit, Q3, True), (_clearbit, Q3, True), (_bulk_import, Q3, True),
    (_set_field_value, QV, True), (_snapshot, Q3, True),
    (_close_reopen, Q3, True), (_new_slice, Q3, True),
    (_frame_recreate, Q3, True),
    # Placement and steering: the walk keeps serving while a peer's
    # health could reorder owners, a resize is in flight, or anything
    # steers reads away.
    (_node_added, Q3, False), (_resize_begun, Q3, False),
    (_quarantined, Q3, False), (_tier_block, Q3, False),
    (_open_circuit, Q3, False),
]


@pytest.mark.parametrize("event, q, re_engages", MATRIX,
                         ids=[m[0].__name__.strip("_") for m in MATRIX])
def test_invalidation_matrix(holder, event, q, re_engages):
    """Whatever may change a route moves a token: the record is dropped
    (counted) and the next answer is exact."""
    _load(holder, N)
    cluster = new_cluster(["local", "peer"], replica_n=2)
    client = _PeerClient(holder, cluster)
    ex = Executor(holder, host="local", cluster=cluster, client=client,
                  fault=FaultManager(node="local"), use_mesh=True,
                  mesh_min_slices=1)
    before = _oracle(holder, q)
    for _ in range(3):
        assert ex.execute("i", q)[0] == before
    memo = ex.route_memo
    assert memo["hits"] == 2 and memo["invalidated"] == 0
    assert client.calls == 0        # local is an owner of every slice

    event(ex, holder)

    dropped, hits = memo["invalidated"], memo["hits"]
    want = _oracle(holder, q)
    assert ex.execute("i", q)[0] == want
    # a drop is counted where it is noticed: by the read after it
    assert dropped == 0 and memo["invalidated"] >= 1
    assert memo["hits"] == hits     # the read after the event walked
    if event in (_setbit, _bulk_import, _new_slice, _set_field_value):
        assert want > before
    if event is _clearbit:
        assert want == before - 1
    for _ in range(2):
        assert ex.execute("i", q)[0] == want
    if re_engages:
        assert memo["hits"] == hits + 2
    else:
        assert memo["hits"] == hits
        assert ex.planner.memo_get(("route", "i")) is None


def test_steering_ends_and_the_memo_returns(holder):
    _load(holder, N)
    cluster = new_cluster(["local", "peer"], replica_n=2)
    ex = Executor(holder, host="local", cluster=cluster,
                  client=_PeerClient(holder, cluster),
                  fault=FaultManager(node="local"), use_mesh=True,
                  mesh_min_slices=1)
    want = _oracle(holder, Q3)
    ex.execute("i", Q3)
    ex.fault.breakers.force_open("peer", reason="test")
    assert ex.fault.steering()
    assert ex.execute("i", Q3)[0] == want
    assert ex.route_memo["invalidated"] == 1
    ex.fault.breakers.record_success("peer")        # the probe came back
    assert not ex.fault.steering()
    hits = ex.route_memo["hits"]
    for _ in range(3):
        assert ex.execute("i", Q3)[0] == want
    assert ex.route_memo["hits"] == hits + 2
    assert ex.route_memo["invalidated"] == 1


def test_explicit_and_remote_slices_take_the_walk(holder):
    _load(holder, N)
    ex = Executor(holder, host="local", use_mesh=True, mesh_min_slices=1)
    want = _oracle(holder, Q3)
    was = residency.device_cache().snapshot()
    assert ex.execute("i", Q3)[0] == want
    assert ex.execute("i", Q3, slices=list(range(N)))[0] == want
    assert ex.execute("i", Q3, slices=list(range(N)),
                      opt=ExecOptions(remote=True))[0] == want
    assert ex.route_memo == {"hits": 0, "misses": 3, "invalidated": 0}
    # and the slab they looked up is the one the routed read built
    cache = residency.device_cache().snapshot()
    assert cache["entries"] == 3
    assert cache["misses"] - was["misses"] == 3
    assert cache["hits"] - was["hits"] == 6


def test_health_ranked_replicas_keep_the_walk(holder):
    """Remote replicas under a fault manager are ordered by health
    score, which no token follows: such a grouping is never kept."""
    _load(holder, N)
    cluster = new_cluster(["peer", "other", "local"], replica_n=2)
    ex = Executor(holder, host="local", cluster=cluster,
                  client=_PeerClient(holder, cluster),
                  fault=FaultManager(node="local"), use_mesh=True,
                  mesh_min_slices=1)
    want = _oracle(holder, Q3)
    for _ in range(3):
        assert ex.execute("i", Q3)[0] == want
    assert ex.planner.memo_get(("route", "i")) is None
    assert ex.route_memo["hits"] == 0


# -- a writer and four readers -----------------------------------------------


def test_acknowledged_write_is_seen_by_the_next_read(holder):
    """One executor, a writer and four readers: a read SENT after a
    write's acknowledgement sees it, whether its route was reused or
    walked (both happen here)."""
    _load(holder, N)
    ex = Executor(holder, host="local", use_mesh=True, mesh_min_slices=1)
    base = ex.execute("i", Q3)[0]
    assert base == _oracle(holder, Q3)
    acked = [0]         # triples fully acknowledged (each adds 1)
    started = [0]
    stop = threading.Event()
    errors: list = []
    writes = 40

    def writer():
        try:
            for i in range(writes):
                col = (i % N) * SLICE_WIDTH + 2000 + i
                started[0] = i + 1
                for r in range(3):
                    ex.execute("i", f"SetBit(frame=f, rowID={r},"
                                    f" columnID={col})")
                acked[0] = i + 1
                time.sleep(0.004)       # let reads reuse a route too
        except Exception as e:  # noqa: BLE001 - reported by the test
            errors.append(e)
        finally:
            stop.set()

    def reader():
        try:
            while not stop.is_set():
                lo = acked[0]
                got = ex.execute("i", Q3)[0]
                hi = started[0]
                assert base + lo <= got <= base + hi, (lo, got - base, hi)
        except Exception as e:  # noqa: BLE001 - reported by the test
            errors.append(e)
            stop.set()

    threads = [threading.Thread(target=writer)] + [
        threading.Thread(target=reader) for _ in range(4)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)     # more interleavings a second
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[0]
    assert acked[0] == writes
    for _ in range(3):
        assert ex.execute("i", Q3)[0] == base + writes
    assert ex.execute("i", Q3)[0] == _oracle(holder, Q3)
    memo = ex.route_memo
    assert memo["hits"] > 0 and memo["invalidated"] > 0


def test_view_token_is_monotonic_under_racing_writers(holder):
    """Racing bumps may not store the counter backwards: a reader
    compares for equality, so a value seen twice must mean no write
    between."""
    _load(holder, 1)
    view = holder.view("i", "f", VIEW_STANDARD)
    start, per = view.generation, 5000
    threads = [threading.Thread(
        target=lambda: [view.bump() for _ in range(per)])
        for _ in range(4)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert view.generation == start + 4 * per


# -- the count guard ----------------------------------------------------------


class _CountingLock:
    def __init__(self):
        self._mu = threading.Lock()
        self.n = 0

    def __enter__(self):
        self._mu.acquire()
        self.n += 1

    def __exit__(self, *exc):
        self._mu.release()


def _route_cost(tmp_path, monkeypatch, n_slices):
    """Calls one warmed ``Count(Intersect(3 rows))`` makes to the
    per-slice machinery, and holds of the residency cache's lock."""
    h = Holder(str(tmp_path / f"d{n_slices}"))
    h.open()
    try:
        _load(h, n_slices)
        ex = Executor(h, host="local", use_mesh=True, mesh_min_slices=1)
        want = _oracle(h, Q3)
        for _ in range(3):
            assert ex.execute("i", Q3)[0] == want
        counts = {"fnv": 0, "holder.fragment": 0, "view.fragment": 0}

        def counted(name, fn):
            def wrapper(*a, **kw):
                counts[name] += 1
                return fn(*a, **kw)
            return wrapper
        monkeypatch.setattr(topology, "fnv1a_64",
                            counted("fnv", topology.fnv1a_64))
        monkeypatch.setattr(Holder, "fragment",
                            counted("holder.fragment", Holder.fragment))
        monkeypatch.setattr(View, "fragment",
                            counted("view.fragment", View.fragment))
        cache = residency.device_cache()
        lock = _CountingLock()
        monkeypatch.setattr(cache, "_mu", lock)
        hits = ex.route_memo["hits"]
        assert ex.execute("i", Q3)[0] == want
        assert ex.route_memo["hits"] == hits + 1
        monkeypatch.undo()
        counts["cache_lock"] = lock.n
        return counts
    finally:
        h.close()


def test_route_does_not_grow_with_the_slice_count(tmp_path, monkeypatch):
    """A count guard, not a wall-clock one: on a warmed index the route
    of one Count(Intersect(3 rows)) hashes no slice, looks up no more
    fragments at 256 slices than at 8, and takes the residency cache's
    lock no more than once a leaf."""
    small = _route_cost(tmp_path, monkeypatch, 8)
    large = _route_cost(tmp_path, monkeypatch, 256)
    assert small["fnv"] == 0 and large["fnv"] == 0
    assert large["holder.fragment"] == small["holder.fragment"]
    assert large["view.fragment"] == small["view.fragment"]
    assert large["view.fragment"] <= 3      # the planner's samples, not a walk
    assert 0 < large["cache_lock"] <= 3
    assert large["cache_lock"] == small["cache_lock"]


def test_vetoed_leg_leaves_the_lru_untouched(holder):
    """``lookup`` is the cost model's probe: a leg that is then vetoed
    must not have refreshed what it looked at, nor counted a hit."""
    _load(holder, N)
    ex = Executor(holder, host="local", use_mesh=True, mesh_min_slices=1)
    ex.execute("i", Q3)
    cache = residency.device_cache()
    order = list(cache._lru)
    snap = cache.snapshot()
    ex._device_pays = lambda *a, **kw: False        # the veto
    assert ex.execute("i", Q3)[0] == _oracle(holder, Q3)
    assert list(cache._lru) == order
    after = cache.snapshot()
    assert (after["hits"], after["misses"]) == (snap["hits"],
                                                snap["misses"])


def test_debug_vars_publishes_the_counter(holder):
    from pilosa_tpu.server.handler import Handler
    _load(holder, N)
    ex = Executor(holder, host="local", use_mesh=True, mesh_min_slices=1)
    for _ in range(3):
        ex.execute("i", Q3)
    h = Handler.__new__(Handler)
    h.executor = ex
    h.stats = types.SimpleNamespace()
    snap = _expvar(h)
    assert snap["routeMemo"] == {"hits": 2, "misses": 1, "invalidated": 0}


def _expvar(handler) -> dict:
    import json
    resp = handler._handle_expvar(None)
    body = resp.body if hasattr(resp, "body") else resp[2]
    return json.loads(body)
