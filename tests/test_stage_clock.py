"""The stage clock (sched.context): self-time stages that tile a request
from socket to socket, their process totals at /debug/vars
(``queryStages``, ``backgroundTicks``, ``compileLog``) and their
``pilosa.<stage>`` segments on the profiler's clock.
docs/OBSERVABILITY.md "Trace contract" is the operator-facing table."""

import glob
import http.client
import json
import os
import threading
import time

import pytest

from pilosa_tpu.sched import QueryContext
from pilosa_tpu.sched import context as sched_context
from pilosa_tpu.sched.context import StageClock
from pilosa_tpu.server.server import Server

# The connection thread's stages of a device-served Count, and what
# its map-reduce leg runs: on the same thread where the read has one
# local leg (every read of a one-node server), on a pool thread behind
# ``legs_wait`` otherwise (tests/test_inline_leg.py).
REQUEST_STAGES = {"http_read", "parse", "setup", "admission", "execute",
                  "plan", "route", "merge", "finish", "encode",
                  "http_write"}
LEG_STAGES = {"leg", "route", "dispatch", "fetch", "merge"}


def _busy(seconds: float) -> None:
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        pass


def _nested(ctx):
    with ctx.stage("execute"):
        _busy(0.002)
        with ctx.stage("route"):
            _busy(0.001)
            with ctx.stage("pack"):
                time.sleep(0.002)
        with ctx.stage("dispatch"):
            _busy(0.001)


def _repeated(ctx):
    with ctx.stage("execute"):
        for _ in range(5):
            with ctx.stage("route"):
                _busy(0.0005)
            with ctx.stage("fetch"):
                time.sleep(0.0005)


def _raising(ctx):
    with ctx.stage("execute"):
        try:
            with ctx.stage("route"):
                with ctx.stage("dispatch"):
                    raise ValueError("device trouble")
        except ValueError:
            pass
        _busy(0.001)
        with ctx.stage("merge"):
            _busy(0.0005)


def _inline_leg(ctx):
    """A read's lone local leg, as ``_map_reduce`` runs it: bound with
    ``use`` on the request thread itself, under ``execute``."""
    with ctx.stage("execute"):
        with sched_context.use(ctx), sched_context.stage("leg"):
            with sched_context.stage("dispatch"):
                _busy(0.001)
            with sched_context.stage("fetch"):
                time.sleep(0.002)
        with ctx.stage("merge"):
            _busy(0.0005)


def _switched(ctx):
    clock = ctx.clock()
    for name in ("parse", "setup", "finish", "encode"):
        clock.switch(name)
        _busy(0.0005)
        if name == "setup":
            with ctx.stage("execute"):
                time.sleep(0.001)


class TestTiling:
    @pytest.mark.parametrize("body", [_nested, _repeated, _raising,
                                      _switched, _inline_leg],
                             ids=lambda f: f.__name__.strip("_"))
    def test_self_times_sum_to_the_threads_time(self, body):
        """Σ self wall of the thread's stages = last boundary − first,
        whatever the nesting: entering a stage suspends its parent.
        Both ends are the clock's own readings (``start`` and its last
        boundary), so the sum is exact whatever else the machine runs;
        and the CPU of the tiling, read at those two boundaries, lies
        inside two readings of the same clock taken around them."""
        cpu0 = time.thread_time()
        t0 = time.perf_counter()
        clock = StageClock("http_read", start=t0)
        ctx = QueryContext(pql="q", clock=clock)
        body(ctx)
        clock.switch("http_write")
        clock.close()
        cpu1 = time.thread_time()
        own, off = ctx.stage_totals()
        total = sum(a[1] for a in own.values())
        assert total == pytest.approx(clock._wall - t0, rel=0, abs=1e-9)
        assert not off
        assert all(n >= 1 and wall >= 0.0 for n, wall in own.values())
        cpu, off_cpu = ctx.stage_cpu()
        assert 0.0 <= cpu <= cpu1 - cpu0 and off_cpu == 0.0

    def test_self_time_not_inclusive_time(self):
        ctx = QueryContext(pql="q")
        with ctx.stage("execute"):
            with ctx.stage("fetch"):
                time.sleep(0.02)
        own, _ = ctx.stage_totals()
        assert own["fetch"][1] >= 0.02
        assert own["execute"][1] < 0.01      # its child's time is not its
        assert ctx.stage_cpu()[0] < 0.01     # asleep: wall without CPU
        assert ctx.stages["fetch"] == own["fetch"][1]

    def test_exception_unwinds_the_stack(self):
        ctx = QueryContext(pql="q")
        with pytest.raises(ValueError):
            with ctx.stage("execute"):
                with ctx.stage("route"):
                    raise ValueError("x")
        clock = ctx.clock()
        assert clock._stack == []
        with ctx.stage("encode"):
            pass
        assert ctx.stage_totals()[0]["encode"][0] == 1

    def test_two_threads_on_one_context(self):
        """A worker bound with ``use`` charges ITS stages to its own
        clock: the request thread's sum stays a tiling and the worker's
        stages are kept apart."""
        t0 = time.perf_counter()
        clock = StageClock("setup")
        ctx = QueryContext(pql="q", clock=clock)

        def leg():
            with sched_context.use(ctx):
                with sched_context.stage("leg"):
                    with sched_context.stage("dispatch"):
                        _busy(0.002)
                    with sched_context.stage("fetch"):
                        time.sleep(0.003)

        with ctx.stage("execute"):
            t = threading.Thread(target=leg)
            # started inside the wait: on a loaded machine a leg started
            # before it can be in ``fetch`` by the time the wait begins
            with ctx.stage("legs_wait"):
                t.start()
                t.join()
        clock.close()
        t1 = time.perf_counter()
        own, off = ctx.stage_totals()
        assert set(own) == {"setup", "execute", "legs_wait"}
        assert set(off) == {"leg", "dispatch", "fetch"}
        assert (t1 - t0) - 0.0005 <= sum(a[1] for a in own.values()) \
            <= t1 - t0
        assert own["legs_wait"][1] >= off["fetch"][1] >= 0.003
        cpu, off_cpu = ctx.stage_cpu()
        assert 0.0015 <= off_cpu < 0.004     # busy 2 ms, asleep 3 ms
        assert cpu < 0.003                   # the request thread waited
        j = ctx.to_json()
        assert set(j["stages"]) == set(own)
        assert set(j["offThread"]) == set(off)

    def test_stage_is_a_span_on_the_contexts_wall_clock(self):
        from pilosa_tpu.obs.trace import Tracer
        ctx = QueryContext(pql="q")
        trace = Tracer().start(ctx, node="n1")
        with ctx.stage("execute", call="Count"):
            with ctx.stage("fetch"):
                time.sleep(0.002)
        with ctx.span("rpc", peer="b"):
            pass
        spans = {s.name: s for s in trace.spans()}
        assert set(spans) == {"execute", "fetch", "rpc"}
        assert spans["execute"].tags == {"call": "Count"}
        # Whole (inclusive) in the kept trace, self time in the counter.
        assert spans["execute"].dur >= spans["fetch"].dur >= 0.002
        assert abs(spans["execute"].start - ctx.started_wall) < 0.05
        assert "rpc" not in ctx.stages      # a span, not a stage


class TestNoCycles:
    def test_a_finished_query_is_freed_without_the_collector(self):
        """A context, its clocks and its trace form no reference cycle:
        a cycle a request doubled the collector's passes over a
        serving heap (PERF.md, PR 25: +9 % on every read)."""
        import gc
        import weakref
        from pilosa_tpu.obs.trace import Tracer
        gc.collect()
        gc.disable()
        try:
            clock = StageClock("http_read")
            ctx = QueryContext(pql="q", clock=clock)
            Tracer().start(ctx, node="n1")

            def leg():
                with sched_context.use(ctx), sched_context.stage("leg"):
                    pass

            with ctx.stage("execute"):
                t = threading.Thread(target=leg)
                t.start()
                t.join()
            clock.close()
            ref = weakref.ref(ctx)
            del ctx, clock, leg, t
            assert ref() is None
        finally:
            gc.enable()

    def test_a_kept_trace_keeps_its_stage_spans(self):
        """``close`` seals a kept trace: the stage spans are copied in
        before the query (which recorded them) goes away."""
        from pilosa_tpu.obs.trace import Tracer
        tracer = Tracer()
        clock = StageClock("http_read")
        ctx = QueryContext(pql="q", clock=clock)
        trace = tracer.start(ctx, node="n1")
        with ctx.stage("execute"):
            pass
        assert tracer.keep(trace, "requested")
        clock.switch("http_write")
        clock.close()
        del ctx, clock
        assert {s.name for s in trace.spans()} == {
            "http_read", "execute", "http_write"}


def _post(conn, path, body):
    conn.request("POST", path, body)
    resp = conn.getresponse()
    data = resp.read()
    return resp, data


@pytest.fixture
def device_server(tmp_path, monkeypatch):
    """A real server on a real socket whose Counts run on the (virtual,
    CPU) device mesh."""
    monkeypatch.setenv("PILOSA_TPU_MESH_MIN_SLICES", "1")
    s = Server(str(tmp_path / "s"), host="127.0.0.1:0",
               anti_entropy_interval=0, polling_interval=0)
    s.open()
    conn = http.client.HTTPConnection(s.host, timeout=30)
    try:
        assert _post(conn, "/index/i", b"{}")[0].status == 200
        assert _post(conn, "/index/i/frame/f", b"{}")[0].status == 200
        for row in (1, 2, 3):
            for col in (3, 5, 1 << 20 | 7):
                _post(conn, "/index/i/query",
                      f'SetBit(frame="f", rowID={row},'
                      f' columnID={col + row})'.encode())
        yield s, conn
    finally:
        conn.close()
        s.close()


def _vars(conn) -> dict:
    conn.request("GET", "/debug/vars")
    return json.loads(conn.getresponse().read())


def _delta(after: dict, before: dict) -> dict:
    """{stage: its counters' growth}, of the stages that were entered
    in between (the totals are the process's)."""
    grown = {name: {k: a[k] - before.get(name, {}).get(k, 0) for k in a}
             for name, a in after.items()}
    return {name: d for name, d in grown.items() if d["n"]}


class TestQueryStagesTotals:
    N = 6
    PQL = (b'Count(Intersect(Bitmap(frame="f", rowID=1),'
           b' Bitmap(frame="f", rowID=2)))')

    def test_totals_after_n_served_counts(self, device_server):
        _, conn = device_server
        resp, _ = _post(conn, "/index/i/query", self.PQL)   # compiles
        assert json.loads(resp.getheader("X-Pilosa-Stats"))[
            "devicePrograms"] >= 1
        before = _vars(conn)["queryStages"]["read"]
        t0 = time.perf_counter()
        for _ in range(self.N):
            resp, data = _post(conn, "/index/i/query", self.PQL)
            assert resp.status == 200
        elapsed = time.perf_counter() - t0
        # The fold follows the sendall: the next request on the same
        # connection is served after it.
        after = _vars(conn)["queryStages"]["read"]
        assert after["requests"] - before["requests"] == self.N
        stages = _delta(after["stages"], before["stages"])
        off = _delta(after["offThread"], before["offThread"])
        # One node, so every read is one local leg, and that leg runs
        # on the connection thread: its stages are among the request's,
        # nothing waits for a pool thread and nothing ran on one.
        assert REQUEST_STAGES | LEG_STAGES <= set(stages), sorted(stages)
        assert "legs_wait" not in stages and not off, (stages, off)
        for name in ("http_read", "parse", "setup", "admission",
                     "execute", "leg", "finish", "encode", "http_write"):
            assert stages[name]["n"] == self.N, (name, stages[name])
        assert stages["dispatch"]["n"] >= stages["fetch"]["n"] >= self.N
        # CPU is read where a thread's stack fills and empties: one
        # number a thread, under the wall its stages tile, recv to
        # sendall, inside the client's send-to-read (each stage's
        # growth is the difference of two totals rounded to a
        # microsecond: up to one each).
        wall = sum(a["wallUs"] for a in stages.values())
        cpu = after["cpuUs"] - before["cpuUs"]
        assert 0 < cpu <= wall * 1.05
        assert wall <= elapsed * 1e6 + len(stages)
        assert after["offThreadCpuUs"] == before["offThreadCpuUs"]

    def test_writes_fold_under_their_lane(self, device_server):
        _, conn = device_server
        v = _vars(conn)["queryStages"]      # process totals: at least
        assert v["write"]["requests"] >= 9  # the fixture's SetBits
        assert v["write"]["stages"]["commit"]["n"] >= 9

    def test_slow_log_and_debug_queries_show_the_stages(self,
                                                        device_server):
        s, conn = device_server
        s.query_registry.slow_threshold_s = 1e-9    # log every query
        _post(conn, "/index/i/query", self.PQL)
        entry = s.query_registry.slow_queries()[-1]
        assert {"parse", "setup", "admission", "execute", "plan",
                "leg", "dispatch", "fetch"} <= set(entry["stages"])
        assert "legs_wait" not in entry["stages"]
        assert "offThread" not in entry     # the one leg ran right here
        assert entry["legs"] == [{"host": s.host, "slices": 2}]

    def test_pipelined_batch_is_one_set_of_stages(self, device_server):
        s, conn = device_server
        before = _vars(conn)["queryStages"]["write"]
        body = b'SetBit(frame="f", rowID=9, columnID=%d)'
        reqs = b"".join(
            b"POST /index/i/query HTTP/1.1\r\nHost: x\r\n"
            b"Content-Length: %d\r\n\r\n%s" % (len(body % c), body % c)
            for c in (1, 2, 3))
        import socket
        host, port = s.host.split(":")
        with socket.create_connection((host, int(port)), timeout=30) as sk:
            sk.sendall(reqs)
            got = b""
            while got.count(b'{"results"') < 3:
                chunk = sk.recv(65536)
                assert chunk
                got += chunk
        deadline = time.monotonic() + 5.0
        while True:     # the fold follows the sendall on the server
            after = _vars(conn)["queryStages"]["write"]
            if (after["requests"] - before["requests"] == 3
                    or time.monotonic() > deadline):
                break
            time.sleep(0.01)
        assert after["requests"] - before["requests"] == 3
        d = _delta(after["stages"], before["stages"])
        assert d["http_read"]["n"] == d["execute"]["n"] == 1
        assert d["http_write"]["n"] == 1


class TestBackgroundTicksAndCompileLog:
    def test_tick_grows_the_loops_counter(self):
        def ticks():
            return sched_context.stage_totals()["backgroundTicks"].get(
                "test_loop", {"n": 0, "wallUs": 0, "cpuUs": 0})
        before = ticks()
        for _ in range(3):
            with sched_context.background_tick("test_loop"):
                _busy(0.001)
                time.sleep(0.001)
        after = ticks()
        assert after["n"] - before["n"] == 3
        assert after["wallUs"] - before["wallUs"] >= 6000
        assert 2000 <= after["cpuUs"] - before["cpuUs"] \
            <= after["wallUs"] - before["wallUs"]

    def test_a_loop_that_ticked_is_counted(self):
        """The runtime collector's own loop (as the server starts it),
        without the SLO trackers: their gauges are process-wide and a
        burn rate left behind would fail the sentinel's tests."""
        from pilosa_tpu.obs.runtime import RuntimeCollector

        def ticks():
            return sched_context.stage_totals()["backgroundTicks"].get(
                "runtime", {"n": 0, "wallUs": 0, "cpuUs": 0})
        before = ticks()
        rc = RuntimeCollector(interval_s=0.02)
        rc.start()
        try:
            deadline = time.monotonic() + 10.0
            while (ticks()["n"] - before["n"] < 2
                   and time.monotonic() < deadline):
                time.sleep(0.02)
        finally:
            rc.stop()
        after = ticks()
        assert after["n"] - before["n"] >= 2
        assert after["wallUs"] > before["wallUs"]
        assert (after["cpuUs"] - before["cpuUs"]
                <= (after["wallUs"] - before["wallUs"]) * 1.05 + 1000)

    def test_compile_log_names_the_program(self, device_server):
        from pilosa_tpu.parallel import mesh as mesh_mod
        _, conn = device_server
        # Another test of this process may have built (and compiled)
        # the same program: start from empty builder caches.
        for cache in mesh_mod._all_program_caches():
            cache.cache_clear()
        pql = (b'Count(Union(Bitmap(frame="f", rowID=1),'
               b' Bitmap(frame="f", rowID=2), Bitmap(frame="f",'
               b' rowID=3)))')
        assert _post(conn, "/index/i/query", pql)[0].status == 200
        log = _vars(conn)["compileLog"]
        assert log and len(log) <= 16
        last = log[-1]
        assert last["program"] == "count_exprs_n1_k3"
        assert last["seconds"] > 0 and last["at"] > 0
        assert len(last["shapes"]) == 3      # one slab a leaf

    def test_program_name_reaches_xla(self):
        """The stable name is the jitted function's name, so the XLA
        module is ``jit_<name>`` and not ``jit_fn`` for every program."""
        import numpy as np
        from pilosa_tpu.parallel import mesh as mesh_mod
        from pilosa_tpu.parallel import programs
        mesh = mesh_mod.make_mesh()
        prog = programs.count_exprs_program(
            mesh, (("and", ("leaf", 0), ("leaf", 1)),), 2)
        assert prog.__name__ == "count_exprs_n1_k2"
        n_dev = mesh.shape[mesh_mod.AXIS_SLICES]
        slab = mesh_mod.shard_slices(
            mesh, np.zeros((n_dev, 64), np.uint32))
        text = prog.__wrapped__.lower(slab, slab).as_text()
        assert "jit_count_exprs_n1_k2" in text


class TestProfilerClock:
    def test_flat_segments_on_the_host_plane(self, device_server,
                                             tmp_path):
        """A profiler session around served queries: ``pilosa.<stage>``
        events on /host:CPU, one at a time per thread (never nested; a
        collector pass, ``pilosa.gc.gen<g>``, lies inside the stage of
        whichever thread it began on and is left out of that rule)."""
        import jax
        from jax.profiler import ProfileData
        _, conn = device_server
        pql = TestQueryStagesTotals.PQL
        _post(conn, "/index/i/query", pql)          # compiled outside
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path / "trace"),
                                 profiler_options=opts)
        try:
            for _ in range(3):
                assert _post(conn, "/index/i/query", pql)[0].status == 200
            with sched_context.background_tick("test_loop"):
                pass
        finally:
            jax.profiler.stop_trace()
        path = glob.glob(os.path.join(str(tmp_path / "trace"), "plugins",
                                      "profile", "*", "*.xplane.pb"))[0]
        names = set()
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                evs = sorted((e.start_ns, e.start_ns + e.duration_ns,
                              e.name) for e in line.events
                             if e.name.startswith("pilosa.")
                             and not e.name.startswith("pilosa.gc."))
                names.update(e[2] for e in evs)
                for a, b in zip(evs, evs[1:]):
                    assert a[1] <= b[0], (line.name, a, b)
        assert {"pilosa.fetch", "pilosa.route", "pilosa.dispatch",
                "pilosa.http_read", "pilosa.http_write",
                "pilosa.bg.test_loop"} <= names, sorted(names)
