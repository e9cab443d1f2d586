"""The lone local leg (ISSUE 28): a map-reduce fan-out that is exactly
one group owned by this node runs on the calling thread; every other
fan-out (more than one group, a remote node, a resize in flight, a
failover re-map) goes through the ``node`` pool as before.

The mechanism must be ENGAGED where a test says so and NOT engaged
where it says so: every test reads ``Executor.legs`` (what
``/debug/vars.legs`` serves). The pooled path is the oracle: the same
data behind a two-node cluster whose fan-out has two groups."""

import threading
import time

import pytest
from test_pod_unit import make_pod
from test_route_memo import _load, _PeerClient

from pilosa_tpu.cluster.topology import new_cluster
from pilosa_tpu.errors import QueryCancelledError, QueryDeadlineError
from pilosa_tpu.executor import ExecOptions, Executor
from pilosa_tpu.fault import FaultManager
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.obs import metrics as obs_metrics
from pilosa_tpu.parallel import mesh as mesh_mod
from pilosa_tpu.parallel import residency
from pilosa_tpu.sched import QueryContext
from pilosa_tpu.sched import context as sched_context
from pilosa_tpu.sched.context import StageClock
from pilosa_tpu.server.server import Server

N = 8           # slices; test_route_memo's four rows and field ``v``
QUERIES = {
    "Count": ("Count(Intersect(Bitmap(frame=f, rowID=0),"
              " Bitmap(frame=f, rowID=1), Bitmap(frame=f, rowID=2)))"),
    "Bitmap": "Union(Bitmap(frame=f, rowID=1), Bitmap(frame=f, rowID=3))",
    # with a source row and a threshold: the sourceless form has a
    # single pass of its own on one node and the plain with-source form
    # one device program (Executor._topn_src_device); neither fans out
    "TopN": "TopN(Bitmap(frame=f, rowID=0), frame=f, n=3, threshold=2)",
    "Sum": "Sum(frame=f, field=v)",
}
Q = QUERIES["Count"]
LEG_STAGES = {"leg", "route", "dispatch", "fetch", "merge"}


def _plain(result):
    """A result as something ``==`` compares."""
    if hasattr(result, "bits"):
        return result.bits().tolist()
    if isinstance(result, list):
        return [(p.id, p.count) for p in result]
    return result


def _single(holder, n_dev=None) -> Executor:
    if n_dev is None:
        return Executor(holder, host="local", use_mesh=False)
    ex = Executor(holder, host="local", use_mesh=True, mesh_min_slices=1)
    ex._mesh = mesh_mod.make_mesh(n_dev)
    return ex


def _two_nodes(holder, replica_n=1, fault=None, **kw):
    cluster = new_cluster(["local", "peer"], replica_n=replica_n)
    client = _PeerClient(holder, cluster)
    ex = Executor(holder, host="local", cluster=cluster, client=client,
                  fault=fault, **kw)
    return ex, client, cluster


def _clocked(ex, pql, **opts):
    """(answer, the request thread's stages, the other threads', ctx)
    of one query served under a stage clock of this thread."""
    clock = StageClock("setup")
    ctx = QueryContext(pql=pql, clock=clock)
    try:
        got = ex.execute("i", pql, None, ExecOptions(ctx=ctx, **opts))[0]
    finally:
        clock.close()
    own, off = ctx.stage_totals()
    return got, own, off, ctx


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "data"))
    h.open()
    _load(h, N)
    yield h
    h.close()


@pytest.fixture(autouse=True)
def _fresh_residency():
    residency.device_cache().clear()
    yield
    residency.device_cache().clear()


# -- engaged: one local group --------------------------------------------------


@pytest.mark.parametrize("n_dev", [None, 1, 4, 8],
                         ids=["host", "1dev", "4dev", "8dev"])
@pytest.mark.parametrize("call", list(QUERIES))
def test_one_local_group_runs_inline_and_equals_the_pooled_oracle(
        holder, call, n_dev):
    """Every map-reduce call of a one-node server, host-placed and on a
    mesh of 1 / 4 / 8 devices: the leg ran on the calling thread (no
    fan-out went to the pool) and the answer is the pooled path's."""
    pql = QUERIES[call]
    oracle, client, _ = _two_nodes(holder, use_mesh=False)
    ex = _single(holder, n_dev)
    try:
        want = _plain(oracle.execute("i", pql)[0])
        assert oracle.legs["pooled"] >= 1 and oracle.legs["inline"] == 0
        assert client.calls >= 1            # a remote leg: two groups
        assert _plain(ex.execute("i", pql)[0]) == want
        assert ex.legs["inline"] >= 1 and ex.legs["pooled"] == 0
        assert "node" not in ex._pools      # the pool was never built
    finally:
        ex.close()
        oracle.close()


@pytest.mark.parametrize("n_dev", [None, 1, 4, 8],
                         ids=["host", "1dev", "4dev", "8dev"])
def test_inline_leg_opens_its_stages_on_the_calling_thread(holder, n_dev):
    """The leg's stages land on the request thread's clock, which still
    tiles the request; there is no ``legs_wait`` and nothing off the
    thread; the leg is on the context's list as before."""
    ex = _single(holder, n_dev)
    try:
        want = ex.execute("i", Q)[0]        # compiles, packs, uploads
        t0 = time.perf_counter()
        got, own, off, ctx = _clocked(ex, Q)
        t1 = time.perf_counter()
    finally:
        ex.close()
    assert got == want
    assert ex.legs == {"inline": 2, "pooled": 0}
    assert "leg" in own and "legs_wait" not in own
    if n_dev is not None:
        assert LEG_STAGES <= set(own), sorted(own)
        assert not off                      # a host leg has its slice pool
    assert own["leg"][0] == 1
    assert ctx.legs == [{"host": "local", "slices": N}]
    assert ctx.stage_cpu()[1] == 0.0 or n_dev is None
    total = sum(a[1] for a in own.values())
    assert (t1 - t0) - 0.002 <= total <= t1 - t0


def test_a_peer_serving_its_own_slices_runs_inline(holder):
    """``remote=True``: the sub-query's slices are all this node's."""
    cluster = new_cluster(["local", "peer"], replica_n=1)
    ex = Executor(holder, host="peer", cluster=cluster, use_mesh=False)
    mine = [s for s in range(N)
            if cluster.fragment_nodes("i", s)[0].host == "peer"]
    assert mine
    whole = _single(holder)
    want = whole.execute("i", Q, slices=mine)[0]
    assert ex.execute("i", Q, slices=mine,
                      opt=ExecOptions(remote=True))[0] == want
    assert ex.legs == {"inline": 1, "pooled": 0}


# -- not engaged: more than one group, a resize, a failover --------------------


def test_two_nodes_go_through_the_pool_and_report_legs_wait(holder):
    ex, client, _ = _two_nodes(holder, use_mesh=True, mesh_min_slices=1)
    want = _single(holder).execute("i", Q)[0]
    got, own, off, ctx = _clocked(ex, Q)
    assert got == want
    assert ex.legs == {"inline": 0, "pooled": 1}
    assert client.calls == 1
    assert sorted(leg["host"] for leg in ctx.legs) == ["local", "peer"]
    assert "legs_wait" in own and "leg" not in own
    assert {"leg", "dispatch", "fetch"} <= set(off), sorted(off)
    # each peer served its own slices on ITS calling thread
    assert client.peers["peer"].legs == {"inline": 1, "pooled": 0}


def test_one_remote_group_goes_through_the_pool(holder):
    """A lone leg that is not this node's is a pooled fan-out."""
    ex, client, cluster = _two_nodes(holder, use_mesh=False)
    theirs = [s for s in range(N)
              if cluster.fragment_nodes("i", s)[0].host == "peer"]
    want = _single(holder).execute("i", Q, slices=theirs)[0]
    assert ex.execute("i", Q, slices=theirs)[0] == want
    assert ex.legs == {"inline": 0, "pooled": 1} and client.calls == 1


def test_a_resize_in_flight_goes_through_the_pool(holder):
    """While slices migrate a read may fan out as double-read legs: the
    whole fan-out stays on the pool, even where this node owns every
    slice; once the resize is over the lone leg is inline again."""
    cluster = new_cluster(["local"])
    ex = Executor(holder, host="local", cluster=cluster, use_mesh=False)
    want = ex.execute("i", Q)[0]
    assert ex.legs == {"inline": 1, "pooled": 0}
    cluster.install_resize("r1", ["local", "new"])
    got, own, off, _ = _clocked(ex, Q)
    assert got == want
    assert ex.legs == {"inline": 1, "pooled": 1}
    assert "legs_wait" in own and "leg" in off
    assert cluster.abort_resize("r1")
    assert ex.execute("i", Q)[0] == want
    assert ex.legs == {"inline": 2, "pooled": 1}


def test_a_pod_coordinator_goes_through_the_pool(holder):
    """A pod's "local" leg is a fan-out over its processes, over the
    network: the caller keeps the pool, and with it the walk-away from
    a peer that stalls past the deadline."""
    ex = _single(holder)
    want = ex.execute("i", Q)[0]
    ex.pod = make_pod(pid=0, n=2, holder=holder)
    stalled = threading.Event()

    def pod_fan_out(index, c, slices, opt, map_fn, reduce_fn):
        stalled.wait(30)        # a pod process that never answers
        return want

    ex._pod_host_mapper = pod_fan_out
    ctx = QueryContext(pql=Q, timeout_s=0.05)
    t0 = time.monotonic()
    try:
        with pytest.raises(QueryDeadlineError):
            ex.execute("i", Q, None, ExecOptions(ctx=ctx))
        walked_away = time.monotonic() - t0
    finally:
        stalled.set()
        ex.close()
    assert ex.legs == {"inline": 1, "pooled": 1}
    assert walked_away < 5.0


def _fail_local_legs(ex, error):
    def boom(slices, map_fn, reduce_fn):
        raise error
    ex._mapper_local = boom


def test_a_failed_inline_leg_is_remapped_on_the_survivor(holder):
    """Two replicas, this node preferred: the lone local leg runs here
    and fails; its slices are re-mapped on the surviving replica
    THROUGH THE POOL, flagged and counted as a pooled leg's failure
    is."""
    ex, client, _ = _two_nodes(
        holder, replica_n=2, fault=FaultManager(node="local"),
        use_mesh=False)
    want = _single(holder).execute("i", Q)[0]
    assert ex.execute("i", Q)[0] == want
    assert ex.legs == {"inline": 1, "pooled": 0} and client.calls == 0
    _fail_local_legs(ex, RuntimeError("local disk trouble"))
    counted = obs_metrics.FAILOVER_SLICES.labels("local").value
    got, own, off, ctx = _clocked(ex, Q)
    assert got == want
    assert ex.legs == {"inline": 2, "pooled": 1}
    assert client.calls >= 1
    assert "failover" in ctx.flags
    assert obs_metrics.FAILOVER_SLICES.labels("local").value \
        == counted + N
    assert "legs_wait" in own               # it waited for the survivor


def test_a_failed_inline_leg_with_no_replica_raises_it(holder):
    ex = _single(holder)
    _fail_local_legs(ex, RuntimeError("local disk trouble"))
    with pytest.raises(RuntimeError, match="local disk trouble"):
        ex.execute("i", Q)
    # the re-map found no owner left: no second fan-out
    assert ex.legs == {"inline": 1, "pooled": 0}


# -- deadline and cancel --------------------------------------------------------


def _dead_in_the_leg(ctx, how):
    """A ``_mapper_local`` under which the query dies once the leg has
    begun; the next cooperative check below it (here the one the
    dispatch layer makes on the thread's bound query) stops the leg."""
    def mapper(slices, map_fn, reduce_fn):
        if how == "cancelled":
            ctx.cancel("test")
        else:
            time.sleep(max(0.0, ctx.remaining()) + 0.005)
        sched_context.check_current()
        raise AssertionError("the leg outlived its query")
    return mapper


@pytest.mark.parametrize("how, error", [
    ("expired", QueryDeadlineError), ("cancelled", QueryCancelledError)])
def test_a_query_that_dies_in_its_inline_leg_surfaces_unchanged(
        holder, how, error):
    """The leg's own cooperative checks stop it; the error is the
    query's, not a node's: no re-map, no failover flag."""
    ex, client, _ = _two_nodes(
        holder, replica_n=2, fault=FaultManager(node="local"),
        use_mesh=False)
    ctx = QueryContext(pql=Q, timeout_s=0.05)
    ex._mapper_local = _dead_in_the_leg(ctx, how)
    with pytest.raises(error):
        ex.execute("i", Q, None, ExecOptions(ctx=ctx))
    assert ex.legs == {"inline": 1, "pooled": 0}
    assert client.calls == 0 and "failover" not in ctx.flags


@pytest.mark.parametrize("how, error", [
    ("expired", QueryDeadlineError), ("cancelled", QueryCancelledError)])
def test_a_dead_query_never_starts_its_leg(holder, how, error):
    ex = _single(holder)
    ctx = QueryContext(pql=Q, timeout_s=0.001)
    if how == "cancelled":
        ctx.cancel("test")
    else:
        time.sleep(0.005)
    with pytest.raises(error):
        ex.execute("i", Q, None, ExecOptions(ctx=ctx))
    assert ctx.legs == []


# -- many threads through one executor -----------------------------------------


@pytest.mark.parametrize("n_dev", [None, 1, 4, 8],
                         ids=["host", "1dev", "4dev", "8dev"])
def test_eight_threads_through_one_executor(holder, n_dev):
    """Local legs run on their callers' threads, eight at once: the
    same answers, and every fan-out counted (the counter is shared)."""
    ex = _single(holder, n_dev)
    rows = [(0, 1), (0, 2), (1, 3), (0, 1, 2), (1, 2, 3), (0, 1, 2, 3)]
    pqls = ["Count(Intersect(%s))" % ", ".join(
        f"Bitmap(frame=f, rowID={r})" for r in rs) for rs in rows]
    host = _single(holder)
    want = [host.execute("i", q)[0] for q in pqls]
    rounds, errors = 12, []

    def client(k):
        try:
            for i in range(rounds):
                j = (k + i) % len(pqls)
                got = ex.execute("i", pqls[j], None, ExecOptions(
                    ctx=QueryContext(pql=pqls[j])))[0]
                assert got == want[j], (pqls[j], got, want[j])
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append(e)

    threads = [threading.Thread(target=client, args=(k,))
               for k in range(8)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        ex.close()
    assert not errors, errors[:3]
    # counted without a lock: a count may be lost between two threads
    assert ex.legs["pooled"] == 0
    assert 8 * (rounds - 1) <= ex.legs["inline"] <= 8 * rounds


# -- the served surface ----------------------------------------------------------


def test_debug_vars_serves_the_counts(tmp_path):
    import http.client
    import json
    s = Server(str(tmp_path / "s"), host="127.0.0.1:0",
               anti_entropy_interval=0, polling_interval=0)
    s.open()
    conn = http.client.HTTPConnection(s.host, timeout=30)
    try:
        def post(path, body):
            conn.request("POST", path, body)
            resp = conn.getresponse()
            resp.read()
            return resp.status

        def legs():
            conn.request("GET", "/debug/vars")
            return json.loads(conn.getresponse().read())["legs"]

        assert post("/index/i", b"{}") == 200
        assert post("/index/i/frame/f", b"{}") == 200
        assert post("/index/i/query",
                    b'SetBit(frame="f", rowID=1, columnID=3)') == 200
        before = legs()
        for _ in range(3):
            assert post("/index/i/query",
                        b'Count(Bitmap(frame="f", rowID=1))') == 200
        after = legs()
        assert after["inline"] - before["inline"] == 3
        assert after["pooled"] == before["pooled"] == 0
    finally:
        conn.close()
        s.close()


# -- the benchmark's reader of the counts ----------------------------------------


def _run(before, after):
    from cellbench import run_cell
    run = run_cell.Run()
    run.before = None if before is None else {"status": {},
                                              "vars": dict(before)}
    run.after = None if after is None else {"status": {},
                                            "vars": dict(after)}
    return run


def _legs(inline, pooled):
    return {"legs": {"inline": inline, "pooled": pooled}}


@pytest.mark.parametrize("before, after, want", [
    (_legs(400, 0), _legs(400 + 9000, 0), 100.0),
    # the warm-up's fan-outs are not the window's
    (_legs(0, 50), _legs(990, 60), 99.0),
    (_legs(7, 0), _legs(7, 12), 0.0),
    ({}, {}, None),                         # the parent: no such counter
    ({}, _legs(10, 0), None),               # it appeared mid-run
    (_legs(10, 2), _legs(10, 2), None),     # nothing fanned out
    (None, None, None),                     # an untraced run
], ids=["100", "99", "0", "absent", "half", "idle", "untraced"])
def test_inline_leg_pct_reads_the_windows_delta(before, after, want):
    from cellbench.readers import inline_leg
    got = inline_leg.read(_run(before, after))
    assert got == (pytest.approx(want) if want is not None else None)


def test_inline_leg_pct_is_declared_as_its_file_says():
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(root, "cellbench", "metrics",
                           "inline_leg_pct.json")) as f:
        spec = json.load(f)
    entry = next(m for m in bench["per_layer"]
                 if m["name"] == "inline_leg_pct")
    for k, v in entry.items():
        assert k == "workloads" or spec[k] == v, k
    assert spec["reader"] == "inline_leg"
    assert entry["workloads"] == ["c4-count-hot", "c4-count-hot-solo",
                                  "c4-count-hot-mesh4"]
    assert entry["moves"] == "read_p50_ms"
    assert entry["layer"] == "executor + routing"
