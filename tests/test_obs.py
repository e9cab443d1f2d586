"""Observability subsystem (pilosa_tpu/obs): metrics registry +
Prometheus exposition, the legacy-StatsClient bridge, the expvar
histogram-aggregation fix, distributed tracing (unit + in-process
HTTP), the slow-query endpoint, the runtime collector, and the
tracing-off overhead guard."""

import io
import json
import re

import pytest

from pilosa_tpu.executor import Executor
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.obs import metrics as obs_metrics
from pilosa_tpu.obs import trace as obs_trace
from pilosa_tpu.obs.runtime import RuntimeCollector
from pilosa_tpu.server.handler import Handler
from pilosa_tpu.utils.stats import ExpvarStatsClient, MultiStatsClient


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


# -- Prometheus text-exposition parser (the validity check) ------------------

_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"          # metric name
    r"(?:\{((?:[a-zA-Z_][a-zA-Z0-9_]*="     # labels: name=
    r"\"(?:[^\"\\]|\\.)*\",?)*)\})?"        # "escaped value"
    r" (NaN|[-+]?(?:[0-9.eE+-]+|Inf))$")    # value


def parse_exposition(text: str) -> dict:
    """Strict-enough parser for the Prometheus text format 0.0.4:
    every non-comment line must be ``name{labels} value``; TYPE lines
    must precede their family's samples. Returns {family: {"type":
    ..., "samples": [(name, labels-dict, value-str)]}}."""
    families: dict = {}
    typed: dict = {}
    assert text.endswith("\n"), "exposition must end with a newline"
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            _, _, rest = line.partition("# TYPE ")
            name, typ = rest.split()
            assert typ in ("counter", "gauge", "histogram", "summary",
                           "untyped")
            typed[name] = typ
            families.setdefault(name, {"type": typ, "samples": []})
            continue
        if line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable exposition line: {line!r}"
        name, rawlabels, value = m.group(1), m.group(2), m.group(3)
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        fam = base if base in typed else name
        assert fam in typed, f"sample {name} precedes its TYPE line"
        labels = dict(re.findall(r'([a-zA-Z_][a-zA-Z0-9_]*)="'
                                 r'((?:[^"\\]|\\.)*)"', rawlabels or ""))
        families[fam]["samples"].append((name, labels, value))
    return families


class TestExpositionEscaping:
    # The exposition-spec escape matrix: label VALUES escape
    # backslash, double quote, and line feed; HELP text escapes ONLY
    # backslash and line feed (an escaped quote in help is itself a
    # spec violation strict OpenMetrics parsers reject).

    HOSTILE = ('back\\slash', 'quo"te', 'new\nline',
               'all\\three" at\nonce', 'trailing\\', '\\"')

    @staticmethod
    def _unescape(v: str) -> str:
        from pilosa_tpu.obs.federate import unescape_label_value
        return unescape_label_value(v)

    def test_hostile_label_values_round_trip(self):
        """Hostile label values render escaped and parse back to the
        exact original through the existing test parser."""
        reg = obs_metrics.Registry()
        c = reg.counter("pilosa_test_hostile_events_total",
                        labels=("k",))
        for v in self.HOSTILE:
            c.labels(v).inc()
        text = reg.render()
        # Every rendered line must stay single-line (the newline in
        # the value is escaped, not emitted).
        for line in text.splitlines():
            assert "\n" not in line
        fams = parse_exposition(text)
        got = {self._unescape(labels["k"])
               for _n, labels, _v in
               fams["pilosa_test_hostile_events_total"]["samples"]}
        assert got == set(self.HOSTILE), got
        # The OpenMetrics rendering escapes identically (parsed with
        # the production federation parser, which unescapes — the
        # 0.0.4 test parser above is strict about OM counter naming).
        from pilosa_tpu.obs import federate
        om = reg.render(openmetrics=True)
        got_om = {labels["k"] for _n, labels, _v in
                  federate.parse_exposition(om)[
                      "pilosa_test_hostile_events_total"]["samples"]}
        assert got_om == set(self.HOSTILE), got_om

    def test_help_escapes_backslash_newline_but_not_quote(self):
        reg = obs_metrics.Registry()
        reg.counter("pilosa_test_help_events_total",
                    'say "hi" to\na back\\slash')
        text = reg.render()
        help_line = next(ln for ln in text.splitlines()
                         if ln.startswith("# HELP"))
        # Quote NOT escaped; newline and backslash escaped.
        assert 'say "hi" to\\na back\\\\slash' in help_line, help_line
        assert '\\"' not in help_line

    def test_federate_parser_matches_test_parser(self):
        """The production exposition parser (obs.federate — the one
        /metrics/cluster merges through) agrees with this test file's
        parser on hostile values, unescaping included."""
        from pilosa_tpu.obs import federate
        reg = obs_metrics.Registry()
        c = reg.counter("pilosa_test_cross_events_total",
                        labels=("k",))
        for v in self.HOSTILE:
            c.labels(v).inc(2)
        fams = federate.parse_exposition(reg.render())
        got = {labels["k"]: v for _n, labels, v in
               fams["pilosa_test_cross_events_total"]["samples"]}
        assert set(got) == set(self.HOSTILE)
        assert all(v == 2.0 for v in got.values())


class TestRegistry:
    def test_counter_gauge_histogram_render(self):
        reg = obs_metrics.Registry()
        c = reg.counter("pilosa_test_widgets_total", "w", labels=("k",))
        c.labels("a").inc()
        c.labels("a").inc(2)
        g = reg.gauge("pilosa_test_queue_depth")
        g.set(7)
        h = reg.histogram("pilosa_test_latency_seconds",
                          buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(30.0)
        fams = parse_exposition(reg.render())
        (name, labels, value), = fams["pilosa_test_widgets_total"][
            "samples"]
        assert labels == {"k": "a"} and value == "3"
        assert fams["pilosa_test_queue_depth"]["samples"][0][2] == "7"
        hs = {(n, ls.get("le")): v for n, ls, v in
              fams["pilosa_test_latency_seconds"]["samples"]}
        assert hs[("pilosa_test_latency_seconds_bucket", "0.1")] == "1"
        assert hs[("pilosa_test_latency_seconds_bucket", "1")] == "2"
        assert hs[("pilosa_test_latency_seconds_bucket", "+Inf")] == "3"
        assert hs[("pilosa_test_latency_seconds_count", None)] == "3"

    def test_naming_convention_enforced_at_registration(self):
        reg = obs_metrics.Registry()
        with pytest.raises(ValueError):
            reg.counter("pilosa_bad_total")  # too few segments
        with pytest.raises(ValueError):
            reg.counter("pilosa_test_widgets_count")  # not _total
        with pytest.raises(ValueError):
            reg.gauge("queue_depth_things")  # no pilosa prefix
        with pytest.raises(ValueError):
            reg.gauge("pilosa_Bad_Case_value")

    def test_reregistration_returns_same_family(self):
        reg = obs_metrics.Registry()
        a = reg.counter("pilosa_test_events_total", labels=("k",))
        b = reg.counter("pilosa_test_events_total", labels=("k",))
        assert a is b
        with pytest.raises(ValueError):
            reg.gauge("pilosa_test_events_total")

    def test_stats_bridge_feeds_registry(self):
        reg = obs_metrics.Registry()
        bridge = obs_metrics.RegistryStatsClient(reg)
        bridge.count("slowQueries", 3)
        bridge.gauge("indexN", 2)
        bridge.timing("snapshotDurationNs", 2_500_000)  # 2.5 ms
        tagged = bridge.with_tags("index:i")
        tagged.count("setN", 5)
        fams = parse_exposition(reg.render())
        assert fams["pilosa_stats_slow_queries_total"]["samples"][0][2] \
            == "3"
        assert fams["pilosa_stats_index_n_value"]["samples"][0][2] == "2"
        # timing lands as a seconds histogram, ns stripped
        samples = fams["pilosa_stats_snapshot_duration_seconds"][
            "samples"]
        assert any(n.endswith("_count") and v == "1"
                   for n, _, v in samples)
        set_samples = fams["pilosa_stats_set_n_total"]["samples"]
        assert set_samples[0][1]["tags"] == "index:i"

    def test_declared_set_is_importable_and_nonempty(self):
        fams = obs_metrics.default_registry().families()
        assert "pilosa_query_duration_seconds" in fams
        assert "pilosa_compile_cache_misses_total" in fams


class TestExpvarHistogramAggregation:
    def test_histogram_aggregates_not_last_write_wins(self):
        c = ExpvarStatsClient()
        for v in (5.0, 1.0, 9.0):
            c.histogram("lat", v)
        snap = c.snapshot()["lat"]
        assert snap == {"count": 3, "sum": 15.0, "min": 1.0,
                        "max": 9.0, "last": 9.0}

    def test_timing_same_semantics(self):
        c = ExpvarStatsClient()
        c.timing("t", 100.0)
        c.timing("t", 300.0)
        snap = c.snapshot()["t"]
        assert snap["count"] == 2 and snap["sum"] == 400.0

    def test_snapshot_copies_do_not_tear(self):
        c = ExpvarStatsClient()
        c.histogram("h", 1.0)
        snap = c.snapshot()
        c.histogram("h", 2.0)
        assert snap["h"]["count"] == 1  # not a live reference

    def test_multi_snapshot_merges_children(self):
        a, b = ExpvarStatsClient(), ExpvarStatsClient()
        a.count("x", 1)
        b.count("y", 2)
        multi = MultiStatsClient([a, b])
        snap = multi.snapshot()
        assert snap["x"] == 1 and snap["y"] == 2


class TestMetricsEndpoint:
    def test_metrics_valid_and_has_query_latency(self, handler, holder):
        holder.create_index_if_not_exists("i") \
            .create_frame_if_not_exists("f")
        status, _, _ = call(
            handler, "POST", "/index/i/query",
            b'SetBit(frame="f", rowID=1, columnID=10)')
        assert status == 200
        status, _, _ = call(handler, "POST", "/index/i/query",
                            b'Count(Bitmap(frame="f", rowID=1))')
        assert status == 200
        status, headers, body = call(handler, "GET", "/metrics")
        assert status == 200
        assert headers["Content-Type"].startswith("text/plain")
        fams = parse_exposition(body.decode())
        lat = fams["pilosa_query_duration_seconds"]
        assert lat["type"] == "histogram"
        counts = [(ls, v) for n, ls, v in lat["samples"]
                  if n.endswith("_count")]
        by_call = {(ls["call"], ls["lane"], ls["status"]): v
                   for ls, v in counts}
        assert int(by_call[("Count", "read", "200")]) >= 1
        assert int(by_call[("SetBit", "write", "200")]) >= 1

    def test_import_counter(self, handler, holder):
        import numpy as np
        from pilosa_tpu.proto import internal_pb2 as pb
        holder.create_index_if_not_exists("i") \
            .create_frame_if_not_exists("f")
        before = obs_metrics.IMPORT_BITS.labels("bits").value
        req = pb.ImportRequest(Index="i", Frame="f", Slice=0,
                               RowIDs=[1, 1], ColumnIDs=[3, 4])
        status, _, _ = call(handler, "POST", "/import",
                            req.SerializeToString(),
                            content_type="application/x-protobuf",
                            headers={"Accept":
                                     "application/x-protobuf"})
        assert status == 200
        assert obs_metrics.IMPORT_BITS.labels("bits").value \
            == before + 2
        assert np is not None


class TestSlowQueryEndpoint:
    def test_slow_log_over_http(self, holder):
        from pilosa_tpu.sched import QueryRegistry
        ex = Executor(holder, host="local", use_mesh=False)
        registry = QueryRegistry(slow_threshold_s=0.0 + 1e-9)
        h = Handler(holder, ex, host="local", registry=registry)
        holder.create_index_if_not_exists("i") \
            .create_frame_if_not_exists("f")
        status, headers, _ = call(h, "POST", "/index/i/query",
                                  b'Count(Bitmap(frame="f", rowID=1))')
        assert status == 200
        qid = headers["X-Pilosa-Query-Id"]
        status, _, body = call(h, "GET", "/debug/queries/slow")
        assert status == 200
        entries = json.loads(body)["slow"]
        assert entries and entries[-1]["id"] == qid
        assert "execute" in entries[-1]["stages"]
        ex.close()


class TestTracing:
    def test_per_request_opt_in_records_spans(self, handler, holder):
        holder.create_index_if_not_exists("i") \
            .create_frame_if_not_exists("f")
        status, headers, _ = call(
            handler, "POST", "/index/i/query?trace=1",
            b'Count(Bitmap(frame="f", rowID=1))')
        assert status == 200
        qid = headers["X-Pilosa-Query-Id"]
        status, _, body = call(handler, "GET", "/debug/traces")
        listing = json.loads(body)
        assert [t for t in listing["traces"] if t["id"] == qid]
        status, _, body = call(handler, "GET", f"/debug/traces/{qid}")
        assert status == 200
        chrome = json.loads(body)
        names = {e["name"] for e in chrome["traceEvents"]}
        # parse → admission → execute (map_reduce + local leg + merge)
        # → encode, plus the perfetto process-name metadata.
        assert {"parse", "admission", "execute", "map_reduce", "leg",
                "merge", "encode", "process_name"} <= names
        for e in chrome["traceEvents"]:
            if e["name"] != "process_name":
                assert e["ph"] == "X" and e["dur"] >= 1
        assert chrome["otherData"]["traceId"] == qid

    def test_trace_404_and_listing_shape(self, handler):
        status, _, _ = call(handler, "GET", "/debug/traces/nope")
        assert status == 404
        status, _, body = call(handler, "GET", "/debug/traces")
        assert status == 200
        assert json.loads(body)["enabled"] is False

    def test_remote_leg_piggybacks_spans_header(self, holder):
        """A remote (forwarded) query that carries X-Pilosa-Trace
        returns its spans in the response header — the stitching
        contract the cluster client consumes."""
        ex = Executor(holder, host="local", use_mesh=False)
        h = Handler(holder, ex, host="local")
        holder.create_index_if_not_exists("i") \
            .create_frame_if_not_exists("f")
        from pilosa_tpu.server import codec
        body = codec.encode_query_request(
            'Count(Bitmap(frame="f", rowID=1))', [0], remote=True)
        status, headers, _ = call(
            h, "POST", "/index/i/query", body,
            content_type="application/x-protobuf",
            headers={"X-Pilosa-Trace": "1",
                     "X-Pilosa-Query-Id": "stitchme",
                     "Accept": "application/x-protobuf"})
        assert status == 200
        spans = json.loads(headers[obs_trace.SPANS_HEADER])
        names = {row[0] for row in spans}
        assert "execute" in names and "map_reduce" in names
        assert headers["X-Pilosa-Query-Id"] == "stitchme"
        ex.close()

    def test_stitched_remote_spans_merge_into_trace(self):
        trace = obs_trace.Trace("q1", node="coord")
        remote = obs_trace.Trace("q1", node="peer")
        remote.add_span("execute", 100.0, 0.5)
        trace.add_span("rpc", 99.9, 0.7)
        trace.add_remote_json(remote.spans_json())
        spans = trace.spans()
        assert {s.node for s in spans} == {"coord", "peer"}
        chrome = trace.to_chrome()
        procs = {e["args"]["name"] for e in chrome["traceEvents"]
                 if e["name"] == "process_name"}
        assert procs == {"coord", "peer"}

    def test_spans_json_respects_wire_budget(self):
        """The piggyback header must stay under http.client's 64 KB
        header-line limit no matter how many spans a leg recorded —
        over budget, the newest spans drop, never the parse/admission
        prefix."""
        trace = obs_trace.Trace("q", node="n" * 40)
        for i in range(obs_trace.MAX_SPANS):
            trace.add_span(f"span_{i}", float(i), 0.5,
                           tags={"detail": "x" * 80})
        wire = trace.spans_json()
        assert len(wire) <= obs_trace.Trace._WIRE_BYTES
        rows = json.loads(wire)
        assert rows and rows[0][0] == "span_0"  # prefix kept
        # And a small trace round-trips untruncated.
        small = obs_trace.Trace("q2")
        small.add_span("a", 1.0, 0.1)
        assert len(json.loads(small.spans_json())) == 1

    def test_span_cap_drops_not_grows(self):
        trace = obs_trace.Trace("q", max_spans=4)
        for i in range(10):
            trace.add_span(f"s{i}", 0.0, 0.1)
        assert len(trace.spans()) == 4
        assert trace.dropped == 6
        assert trace.summary()["dropped"] == 6


class TestOverheadGuard:
    def test_tracing_off_is_default_and_allocates_no_spans(
            self, handler, holder, monkeypatch):
        """With tracing at defaults a query must not construct a
        single Span object, and nothing lands in the trace ring."""
        from pilosa_tpu.utils.config import TraceConfig
        assert TraceConfig().enabled is False
        assert handler.tracer.enabled is False

        made = []
        real = obs_trace.Span

        class CountingSpan(real):
            def __init__(self, *a, **kw):
                made.append(1)
                super().__init__(*a, **kw)

        monkeypatch.setattr(obs_trace, "Span", CountingSpan)
        holder.create_index_if_not_exists("i") \
            .create_frame_if_not_exists("f")
        status, _, _ = call(handler, "POST", "/index/i/query",
                            b'Count(Bitmap(frame="f", rowID=1))')
        assert status == 200
        assert made == []
        assert handler.tracer.traces() == []

    def test_span_current_nop_fast_path(self):
        """The one emitter's no-op paths: no query bound to the thread
        → neither a stage nor a span allocates; a bound but untraced
        query runs its stages (the counters are always on) and records
        no span."""
        from pilosa_tpu.sched import QueryContext
        from pilosa_tpu.sched import context as sched_context
        assert sched_context.span("x") is sched_context.NOP
        assert sched_context.stage("x") is sched_context.NOP
        ctx = QueryContext(pql="q")  # no trace attached
        assert ctx.span("x") is sched_context.NOP
        with sched_context.use(ctx):
            assert sched_context.span("x") is sched_context.NOP
            with sched_context.stage("route"):
                pass
        assert ctx.stage_totals()[0]["route"][0] == 1
        assert not hasattr(obs_trace, "span_current")


class TestRuntimeCollector:
    def test_collect_shapes(self, holder):
        holder.create_index_if_not_exists("i") \
            .create_frame_if_not_exists("f").set_bit("standard", 1, 2)
        rc = RuntimeCollector(holder=holder)
        snap = rc.collect()
        assert snap["holder"]["indexes"] == 1
        assert snap["holder"]["fragments"] >= 1
        assert snap["threads"]["live"] >= 1
        assert {"hits", "misses", "programs"} <= set(
            snap["compileCache"])
        assert rc.snapshot() is not None

    def test_compile_stats_count_builds(self):
        from pilosa_tpu.parallel import mesh as mesh_mod
        before = mesh_mod.compile_stats()
        mesh = mesh_mod.make_mesh()
        import numpy as np
        n_dev = mesh.shape[mesh_mod.AXIS_SLICES]
        slab = mesh_mod.shard_slices(
            mesh, np.zeros((n_dev, 64), np.uint32))
        # An uncommon expr shape forces a fresh program build + first
        # call; a repeat of the same call must be a pure cache hit.
        expr = ("or", ("and", ("leaf", 0), ("leaf", 1)),
                ("andnot", ("leaf", 1), ("leaf", 0)))
        mesh_mod.count_expr_sharded(mesh, expr, [slab, slab])
        mid = mesh_mod.compile_stats()
        assert mid["programsBuilt"] > before["programsBuilt"]
        assert mid["firstCalls"] > before["firstCalls"]
        assert mid["compileSeconds"] > before["compileSeconds"]
        mesh_mod.count_expr_sharded(mesh, expr, [slab, slab])
        after = mesh_mod.compile_stats()
        assert after["programsBuilt"] == mid["programsBuilt"]
        assert after["hits"] > mid["hits"]

    def test_roaring_op_counts(self):
        from pilosa_tpu.storage import roaring
        before = roaring.op_counts()
        a = roaring.Bitmap(1, 2, 3)
        b = roaring.Bitmap(2, 3, 4)
        a.intersect(b)
        after = roaring.op_counts()
        key = ("intersect", "array_array")
        assert after[key] == before[key] + 1
