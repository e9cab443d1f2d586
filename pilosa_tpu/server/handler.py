"""HTTP API: the reference's full route table as a WSGI application.

Reference: handler.go (route table at handler.go:82-120). Content
negotiation between JSON and ``application/x-protobuf`` mirrors
handler.go:811-893; strict unknown-key validation of index/frame options
mirrors handler.go:299-351,577-610.

WSGI keeps the handler framework-free: tests call the app in-process
(no sockets), and server.py serves it with the stdlib threading WSGI
server — the Python analogue of the reference's net/http.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import re
import threading
import time
from typing import Callable, Optional
from urllib.parse import parse_qs

import numpy as np

from .. import __version__
from ..cluster import generations as gens_mod
from ..cluster.broadcast import (NOP_BROADCASTER, CancelQueryMessage,
                                 unmarshal_message)
from ..errors import (FrameExistsError, IndexExistsError, PilosaError,
                      QueryCancelledError, QueryDeadlineError,
                      QueryKilledError, SliceUnavailableError,
                      validate_label)
from ..fault import diskfull as fault_diskfull
from ..obs import accounting as obs_accounting
from ..obs import capture as obs_capture
from ..obs import metrics as obs_metrics
from ..obs import profile as obs_profile
from ..obs import trace as obs_trace
from ..plan import record as plan_record
from ..sched import (KILL_POLICY, KILLED_BY_HEADER, LANE_ADMIN, LANE_READ,
                     LANE_WRITE, AdmissionFullError, QueryContext,
                     QueryRegistry)
from ..sched import context as sched_context
from ..models.frame import Field, FrameOptions
from ..models.index import IndexOptions
from ..pql import parser as pql
from ..proto import internal_pb2 as pb
from ..storage import wal as storage_wal
from ..storage.attrs import diff_blocks
from ..storage.bitmap import Bitmap
from ..utils import timequantum as tq
from ..utils.streams import CappedReader
from . import codec

_PROTOBUF = "application/x-protobuf"

# JSON keys accepted in POST /index and POST /frame options
# (handler.go:299-351 validates against the Go struct tags).
_VALID_INDEX_OPTIONS = {"columnLabel", "timeQuantum"}
_VALID_FRAME_OPTIONS = {"rowLabel", "inverseEnabled", "cacheType",
                        "cacheSize", "timeQuantum", "fields"}


class HTTPError(Exception):
    def __init__(self, status: int, message: str, headers=None):
        super().__init__(message)
        self.status = status
        self.message = message
        self.headers = headers or []


class Request:
    """Decoded WSGI request."""

    def __init__(self, environ: dict, vars: dict[str, str]):
        self.environ = environ
        self.vars = vars
        self.query = {k: v[0] for k, v in
                      parse_qs(environ.get("QUERY_STRING", "")).items()}

    @property
    def content_type(self) -> str:
        return self.environ.get("CONTENT_TYPE", "")

    @property
    def accept(self) -> str:
        return self.environ.get("HTTP_ACCEPT", "")

    def body(self) -> bytes:
        # Missing/invalid Content-Length reads as empty — an unbounded
        # read() on the live socket would block the worker thread.
        try:
            length = int(self.environ.get("CONTENT_LENGTH") or 0)
        except ValueError:
            length = 0
        stream = self.environ.get("wsgi.input")
        if stream is None or length <= 0:
            return b""
        return stream.read(length)

    def body_stream(self):
        """The request body as a bounded file-like, without buffering it
        — restores stream 128 MB+ fragment tars straight to disk."""
        try:
            length = int(self.environ.get("CONTENT_LENGTH") or 0)
        except ValueError:
            length = 0
        stream = self.environ.get("wsgi.input")
        if stream is None or length <= 0:
            return io.BytesIO(b"")
        return CappedReader(stream, length)

    def json(self) -> dict:
        raw = self.body()
        if not raw:
            return {}
        try:
            return json.loads(raw)
        except ValueError as e:
            raise HTTPError(400, f"invalid JSON: {e}")

    def uint_param(self, name: str) -> int:
        v = self.query.get(name)
        if v is None or not v.isdigit():
            raise HTTPError(400, f"{name} required")
        return int(v)


class Response:
    def __init__(self, status: int = 200, body=b"",
                 content_type: str = "application/json",
                 headers=None):
        # body: bytes, or a readable file object (streamed in chunks —
        # used for fragment backups, which can be 128 MB+). headers:
        # extra (name, value) pairs (Retry-After, X-Pilosa-Query-Id).
        self.status = status
        self.body = body
        self.content_type = content_type
        self.headers = headers or []

    @staticmethod
    def json(obj, status: int = 200, headers=None) -> "Response":
        return Response(status, (json.dumps(obj) + "\n").encode(),
                        headers=headers)

    @staticmethod
    def proto(msg, status: int = 200, headers=None) -> "Response":
        return Response(status, msg.SerializeToString(), _PROTOBUF,
                        headers=headers)


def _export_csv_chunks(frag):
    """Vectorized, chunked CSV body: one chunk per roaring container, so
    a 128 MB+ fragment never sits in memory as text (the reference
    streams via csv.Writer over ForEachBit, handler.go:985-1025).

    The WSGI layer drains this generator after the handler returns, so
    it streams from Fragment.snapshot_value_chunks(): a point-in-time
    copy of the compressed container buffers taken under the fragment
    lock — concurrent mutations during the (possibly long) transfer
    can't tear a row mid-stream, and peak memory is bounded by the
    compressed fragment size, not the rendered text."""
    from .. import SLICE_WIDTH
    base = frag.slice * SLICE_WIDTH
    w = np.uint64(SLICE_WIDTH)
    for vals in frag.snapshot_value_chunks():
        rows = (vals // w).tolist()
        cols = (vals % w).tolist()
        yield "".join(f"{r},{base + c}\r\n"
                      for r, c in zip(rows, cols)).encode()


def _stream_chunks(f, chunk_size: int = 1 << 20):
    try:
        while True:
            chunk = f.read(chunk_size)
            if not chunk:
                return
            yield chunk
    finally:
        f.close()


_STATUS_TEXT = {200: "OK", 400: "Bad Request",
                402: "Payment Required",  # cost-policy kill
                404: "Not Found",
                405: "Method Not Allowed", 406: "Not Acceptable",
                409: "Conflict", 412: "Precondition Failed",
                415: "Unsupported Media Type",
                429: "Too Many Requests",
                500: "Internal Server Error", 503: "Service Unavailable",
                504: "Gateway Timeout",
                507: "Insufficient Storage"}  # ENOSPC write-unready


# Import apply lanes: how many /import handlers may be in their APPLY
# stage at once, process-wide. The apply is mostly GIL-holding
# Python/numpy, so unbounded concurrent applies convoy on the GIL and
# run measurably SLOWER than the same blocks queued (1.4x at 4 lanes
# on 2 cores) — the pipelining win comes from decode/wire/WAL of
# other blocks overlapping an apply, which the gate never blocks.
# `pilosa_import_pipeline_depth` counts handlers in the stage
# (applying + gate-queued), so depth > lanes means the pipeline is
# feeding the gate faster than it drains.
_APPLY_LANES = max(1, int(os.environ.get(
    "PILOSA_TPU_IMPORT_APPLY_LANES", "1") or 1))
_APPLY_GATE = threading.BoundedSemaphore(_APPLY_LANES)


class Handler:
    """Router + handlers. Executor is any object with
    ``execute(index, query, slices, opt)`` — the mock seam used by the
    handler tests, mirroring the reference's Handler.Executor interface
    (handler.go:60-62)."""

    def __init__(self, holder, executor, cluster=None, host: str = "",
                 broadcaster=NOP_BROADCASTER, broadcast_handler=None,
                 status_handler=None, stats=None, client_factory=None,
                 pod=None, logger=None, admission=None, registry=None,
                 warmup=None, default_timeout_s: float = 0.0,
                 tracer=None, runtime=None, profiler=None, health=None,
                 accounting: bool = True, fault=None, sampler=None,
                 blackbox=None, watchdog=None, history=None,
                 sentinel=None, federator=None, tenants=None,
                 tenant_slo=None, scrubber=None, repairer=None,
                 tier=None, capture=None):
        from ..utils import logger as logger_mod
        self.logger = logger or logger_mod.NOP
        self.holder = holder
        self.executor = executor
        self.cluster = cluster
        self.host = host
        self.pod = pod  # parallel.pod.Pod when serving as a pod process
        self.broadcaster = broadcaster
        self.broadcast_handler = broadcast_handler
        self.status_handler = status_handler
        self.stats = stats
        # client_factory(host) -> cluster.client.Client; injected to keep
        # handler importable without the client (and mockable in tests).
        self.client_factory = client_factory
        # Query lifecycle (sched subsystem): admission=None means no
        # admission control (bare test handlers); the registry always
        # exists so /debug/queries works on any handler.
        self.admission = admission
        # Multi-tenant QoS (sched.tenants): the tenant registry
        # resolves every request's principal (header > index >
        # default), installs the cost-kill policy, and backs
        # /debug/tenants; tenant_slo is the per-tenant burn tracker
        # (obs.slo.TenantSLOTracker). None = tenant-blind (bare test
        # handlers; tenant metrics still record by index).
        self.tenants = tenants
        self.tenant_slo = tenant_slo
        self.registry = registry if registry is not None \
            else QueryRegistry(logger=self.logger)
        self.warmup = warmup
        self.default_timeout_s = default_timeout_s or 0.0
        # Observability (obs subsystem): a per-node tracer (disabled by
        # default — bare handlers still honor per-request ?trace=1) and
        # the runtime collector behind /status and /metrics freshness.
        self.tracer = tracer if tracer is not None \
            else obs_trace.Tracer(enabled=False)
        self.runtime = runtime
        # Tail sampling (obs.sampler): when wired, EVERY query gets
        # the span buffer and the keep decision runs at query end;
        # None (bare test handlers) keeps the ask-first behavior.
        self.sampler = sampler
        # Flight recorder + stall watchdog (obs.blackbox/obs.watchdog)
        # behind /debug/blackbox*; None serves empty state.
        self.blackbox = blackbox
        self.watchdog = watchdog
        # Fleet observability (obs.history / obs.sentinel /
        # obs.federate): the on-disk metric history behind
        # /debug/metrics/history, the regression sentinel behind
        # /debug/sentinel, and the federator behind /metrics/cluster +
        # /debug/cluster. A bare handler keeps a peerless federator so
        # the cluster routes serve single-node answers.
        self.history = history
        self.sentinel = sentinel
        # Storage integrity (storage.scrub / server.repair) behind
        # /debug/integrity; None (bare handlers) serves the holder's
        # quarantine registry alone.
        self.scrubber = scrubber
        self.repairer = repairer
        # Tiered storage (pilosa_tpu.tier) behind /debug/tier; None
        # (tiering off / bare handlers) serves a disabled stub.
        self.tier = tier
        # Workload capture (obs.capture.CaptureStore) behind
        # /debug/capture*; None (bare handlers) serves a disabled
        # status and captures nothing — the query path pays one
        # ``is not None`` check.
        self.capture = capture
        if federator is None:
            from ..obs.federate import Federator
            federator = Federator(host)
        self.federator = federator
        # Continuous profiler (obs.profile) behind /debug/pprof/flame —
        # the module default is NOT started, so bare handlers serve the
        # route with an empty ring and zero sampling overhead.
        self.profiler = profiler if profiler is not None \
            else obs_profile.get_profiler()
        # Readiness checks behind GET /health (obs.slo.HealthChecker);
        # built lazily from this handler's own wiring when not injected.
        self._health = health
        # Per-handler accounting gate ([metrics] accounting): scoped
        # here, not process-global, so in-process multi-server tests
        # can differ; obs_accounting.enabled() remains a second,
        # module-wide kill switch.
        self.accounting = accounting
        # Fault-tolerance state (fault.FaultManager) behind the
        # /status ``fault`` block; failpoint admin (/debug/failpoints)
        # talks to the process-global registry and works on bare
        # handlers too.
        self.fault = fault
        self.version = __version__
        # (method, regex, handler, admission lane, raw pattern)
        self._routes: list[tuple] = []
        self._add_routes()

    # -- routing -------------------------------------------------------------

    def _route(self, method: str, pattern: str, fn: Callable,
               lane: Optional[str] = None) -> None:
        # {name} segments become named groups matching one path segment.
        # ``lane`` routes the whole handler through that admission lane
        # (the query handler manages its own slot — deadline-aware, and
        # remote legs bypass — so it stays lane=None here). The raw
        # pattern is kept for introspection (the README route-table
        # sweep test walks it).
        regex = re.sub(r"\{(\w+)\}", r"(?P<\1>[^/]+)", pattern)
        self._routes.append((method, re.compile(f"^{regex}$"), fn, lane,
                             pattern))

    def _add_routes(self) -> None:
        # Route table (reference handler.go:82-120).
        r = self._route
        r("GET", "/", self._handle_webui)
        r("GET", "/assets/{file}", self._handle_asset)
        r("GET", "/index", self._handle_get_schema)
        r("GET", "/index/{index}", self._handle_get_index)
        r("POST", "/index/{index}", self._handle_post_index,
          lane=LANE_ADMIN)
        r("DELETE", "/index/{index}", self._handle_delete_index,
          lane=LANE_ADMIN)
        r("POST", "/index/{index}/attr/diff", self._handle_index_attr_diff)
        r("POST", "/index/{index}/frame/{frame}", self._handle_post_frame,
          lane=LANE_ADMIN)
        r("DELETE", "/index/{index}/frame/{frame}",
          self._handle_delete_frame, lane=LANE_ADMIN)
        r("POST", "/index/{index}/query", self._handle_post_query)
        r("POST", "/index/{index}/frame/{frame}/attr/diff",
          self._handle_frame_attr_diff)
        r("POST", "/index/{index}/frame/{frame}/restore",
          self._handle_post_frame_restore, lane=LANE_ADMIN)
        r("PATCH", "/index/{index}/frame/{frame}/time-quantum",
          self._handle_patch_frame_time_quantum, lane=LANE_ADMIN)
        r("GET", "/index/{index}/frame/{frame}/views",
          self._handle_get_frame_views)
        r("GET", "/index/{index}/frame/{frame}/fields",
          self._handle_get_frame_fields)
        r("POST", "/index/{index}/frame/{frame}/field/{field}",
          self._handle_post_frame_field, lane=LANE_ADMIN)
        r("POST", "/index/{index}/frame/{frame}/field/{field}/import",
          self._handle_post_field_import, lane=LANE_WRITE)
        r("PATCH", "/index/{index}/time-quantum",
          self._handle_patch_index_time_quantum, lane=LANE_ADMIN)
        r("GET", "/cluster/resize", self._handle_get_cluster_resize)
        r("POST", "/cluster/resize", self._handle_post_cluster_resize,
          lane=LANE_ADMIN)
        r("GET", "/backup", self._handle_get_backup)
        r("POST", "/backup", self._handle_post_backup,
          lane=LANE_ADMIN)
        r("GET", "/debug/backup", self._handle_debug_backup)
        r("GET", "/debug/topology", self._handle_debug_topology)
        r("GET", "/debug/tenants", self._handle_debug_tenants)
        r("GET", "/debug/queries", self._handle_debug_queries)
        r("GET", "/debug/queries/slow", self._handle_debug_slow_queries)
        r("DELETE", "/debug/queries/{qid}", self._handle_delete_query)
        r("GET", "/debug/traces", self._handle_debug_traces)
        # /summary must register BEFORE the {qid} wildcard or the
        # wildcard swallows it.
        r("GET", "/debug/traces/summary",
          self._handle_debug_traces_summary)
        r("GET", "/debug/traces/{qid}", self._handle_debug_trace)
        r("GET", "/debug/blackbox", self._handle_debug_blackbox)
        r("POST", "/debug/blackbox/dump",
          self._handle_post_blackbox_dump)
        r("GET", "/debug/failpoints", self._handle_debug_failpoints)
        r("POST", "/debug/failpoints", self._handle_post_failpoints)
        r("GET", "/debug/integrity", self._handle_debug_integrity)
        r("POST", "/debug/integrity/scrub",
          self._handle_post_integrity_scrub)
        r("GET", "/debug/tier", self._handle_debug_tier)
        r("GET", "/debug/capture", self._handle_debug_capture)
        r("GET", "/debug/capture/records",
          self._handle_debug_capture_records)
        r("GET", "/debug/vars", self._handle_expvar)
        r("GET", "/debug/metrics/history",
          self._handle_metrics_history)
        r("GET", "/debug/cluster", self._handle_debug_cluster)
        r("GET", "/debug/plans", self._handle_debug_plans)
        r("GET", "/debug/sentinel", self._handle_debug_sentinel)
        r("GET", "/metrics", self._handle_metrics)
        r("GET", "/metrics/cluster", self._handle_metrics_cluster)
        r("GET", "/debug/pprof", self._handle_pprof_index)
        r("GET", "/debug/pprof/", self._handle_pprof_index)
        r("GET", "/debug/pprof/profile", self._handle_pprof_profile)
        r("GET", "/debug/pprof/threads", self._handle_pprof_threads)
        r("GET", "/debug/pprof/heap", self._handle_pprof_heap)
        r("POST", "/debug/pprof/heap", self._handle_pprof_heap_post)
        r("GET", "/debug/pprof/flame", self._handle_pprof_flame)
        r("GET", "/health", self._handle_health)
        r("GET", "/export", self._handle_get_export)
        r("GET", "/fragment/block/data", self._handle_fragment_block_data)
        r("GET", "/fragment/blocks", self._handle_fragment_blocks)
        r("GET", "/fragment/data", self._handle_get_fragment_data)
        r("POST", "/fragment/data", self._handle_post_fragment_data)
        r("POST", "/fragment/import",
          self._handle_post_fragment_import, lane=LANE_WRITE)
        r("GET", "/fragment/nodes", self._handle_fragment_nodes)
        r("GET", "/generations", self._handle_get_generations)
        r("POST", "/import", self._handle_post_import, lane=LANE_WRITE)
        r("GET", "/hosts", self._handle_get_hosts)
        r("GET", "/schema", self._handle_get_schema)
        r("GET", "/slices/max", self._handle_slice_max)
        r("GET", "/status", self._handle_get_status)
        r("GET", "/version", self._handle_get_version)
        r("POST", "/messages", self._handle_post_message)
        r("POST", "/pod/exec", self._handle_pod_exec)

    def __call__(self, environ, start_response):
        method = environ.get("REQUEST_METHOD", "GET")
        path = environ.get("PATH_INFO", "/")
        # HEAD serves through GET handlers with the body dropped —
        # net/http gives the reference this for free.
        head = method == "HEAD"
        if head:
            method = "GET"
        matched_path = False
        for m, regex, fn, lane, _pattern in self._routes:
            match = regex.match(path)
            if match is None:
                continue
            matched_path = True
            if m != method:
                continue
            try:
                if lane is None:
                    resp = fn(Request(environ, match.groupdict()))
                else:
                    # Tenant principal for the non-query lanes
                    # (imports, schema admin): header > {index} path
                    # segment > default — resolved BEFORE the slot is
                    # taken, so the stride/quota accounting charges
                    # the right tenant from the first byte.
                    vars_ = match.groupdict()
                    tenant = (environ.get("HTTP_X_PILOSA_TENANT", "")
                              or vars_.get("index", ""))
                    with self._admitted(lane, tenant=tenant):
                        resp = fn(Request(environ, vars_))
            except HTTPError as e:
                resp = Response(e.status, (e.message + "\n").encode(),
                                "text/plain; charset=utf-8",
                                headers=e.headers)
            except PilosaError as e:
                resp = Response(400, (str(e) + "\n").encode(),
                                "text/plain; charset=utf-8")
            except Exception as e:  # noqa: BLE001 - surface as 500
                self.logger.printf("http error: %s %s: %s", method, path, e)
                resp = Response(500, (str(e) + "\n").encode(),
                                "text/plain; charset=utf-8")
            break
        else:
            status = 405 if matched_path else 404
            resp = Response(status,
                            (_STATUS_TEXT[status] + "\n").encode(),
                            "text/plain; charset=utf-8")
        status_line = (
            f"{resp.status} {_STATUS_TEXT.get(resp.status, 'Unknown')}")
        extra = list(getattr(resp, "headers", ()) or ())
        if isinstance(resp.body, bytes):
            start_response(status_line,
                           [("Content-Type", resp.content_type),
                            ("Content-Length", str(len(resp.body)))]
                           + extra)
            return [] if head else [resp.body]
        # Streamed body: file object (chunked reads) or a generator of
        # byte chunks (CSV export) — either way, never buffered whole.
        start_response(status_line,
                       [("Content-Type", resp.content_type)] + extra)
        if hasattr(resp.body, "read"):
            return _stream_chunks(resp.body)
        return resp.body

    # -- meta ----------------------------------------------------------------

    def _handle_webui(self, req: Request) -> Response:
        # Embedded console (reference webui/ + statik, handler.go:132-145).
        from .webui import page_bytes
        return Response(200, page_bytes(), "text/html; charset=utf-8")

    def _handle_asset(self, req: Request) -> Response:
        # Static console assets (reference handler.go:84 /assets/{file}).
        from .webui import asset
        got = asset(req.vars["file"])
        if got is None:
            raise HTTPError(404, "asset not found")
        body, ctype = got
        return Response(200, body, ctype)

    def _handle_get_version(self, req: Request) -> Response:
        return Response.json({"version": self.version})

    def _handle_get_hosts(self, req: Request) -> Response:
        nodes = self.cluster.nodes if self.cluster else []
        return Response.json([{"host": n.host,
                               "internalHost": n.internal_host}
                              for n in nodes])

    def _handle_get_status(self, req: Request) -> Response:
        # Cold-start warmup state (sched.warmup) and the runtime
        # collector sample (obs.runtime — holder/residency sizes,
        # compile-cache hit/miss counters) ride the JSON forms.
        warm = self.warmup.to_json() if self.warmup is not None else None
        runtime = (self.runtime.snapshot()
                   if self.runtime is not None else None)
        fault = self.fault.snapshot() if self.fault is not None else None
        # Build identity (the JSON face of pilosa_build_info): version,
        # python, jax, backend — same block on every status form.
        from ..obs.runtime import build_info
        build = build_info()
        watchdog = (self.watchdog.snapshot()
                    if self.watchdog is not None else None)
        if self.status_handler is not None:
            cs = self.status_handler.cluster_status()  # pb.ClusterStatus
            if _PROTOBUF in req.accept:
                return Response.proto(cs)
            out = {"status": {"nodes": [
                {"host": ns.Host, "state": ns.State,
                 "indexes": [{"name": ix.Name,
                              "maxSlice": ix.MaxSlice,
                              "slices": list(ix.Slices),
                              "frames": [{"name": f.Name}
                                         for f in ix.Frames]}
                             for ix in ns.Indexes]}
                for ns in cs.Nodes]}}
            out["build"] = build
            if warm is not None:
                out["warmup"] = warm
            if runtime is not None:
                out["runtime"] = runtime
            if fault is not None:
                out["fault"] = fault
            if watchdog is not None:
                out["watchdog"] = watchdog
            return Response.json(out)
        states = self.cluster.node_states() if self.cluster else {}
        out = {"status": {"Nodes": [
            {"Host": h, "State": s} for h, s in sorted(states.items())]}}
        out["build"] = build
        if warm is not None:
            out["warmup"] = warm
        if runtime is not None:
            out["runtime"] = runtime
        if fault is not None:
            out["fault"] = fault
        if watchdog is not None:
            out["watchdog"] = watchdog
        return Response.json(out)

    def _handle_expvar(self, req: Request) -> Response:
        snap = self.stats.snapshot() if hasattr(self.stats, "snapshot") \
            else {}
        # Device-path observability: HBM residency cache + fallback
        # counters (reference exposes runtime internals the same way
        # via expvar, handler.go:1287-1300).
        from ..parallel import residency
        snap = dict(snap)
        snap["deviceBlockCache"] = residency.device_cache().snapshot()
        fallbacks = getattr(self.executor, "device_fallbacks", None)
        if fallbacks is not None:
            # Authoritative value for the stats pipeline's
            # "deviceFallback" counter (executor._note_device_fallback)
            # — one name, one source.
            snap["deviceFallback"] = fallbacks
        vetoes = getattr(self.executor, "cost_vetoes", None)
        if vetoes is not None:
            snap["costModelVetoes"] = vetoes
        mesh_state = getattr(self.executor, "mesh_state", None)
        if mesh_state is not None:
            # The mesh the device programs run on; null before the
            # first device call (docs/OBSERVABILITY.md).
            snap["mesh"] = mesh_state()
        route_memo = getattr(self.executor, "route_memo", None)
        if route_memo is not None:
            # Device-lowered reads by how their route was found: reused
            # whole from its record, or walked (docs/OBSERVABILITY.md).
            snap["routeMemo"] = dict(route_memo)
        legs = getattr(self.executor, "legs", None)
        if legs is not None:
            # Map-reduce fan-outs by where their legs ran: the lone
            # local leg on the calling thread, or the pool
            # (docs/OBSERVABILITY.md).
            snap["legs"] = dict(legs)
        topn = getattr(self.executor, "topn_src", None)
        if topn is not None:
            # With-source TopN reads by where their counts came from,
            # and the whole-frame device forms they read
            # (docs/OBSERVABILITY.md).
            cache = residency.device_cache()
            frames, frame_bytes = cache.frames()
            snap["topn"] = dict(topn, residentFrames=frames,
                                residentFrameBytes=frame_bytes,
                                builds=cache.frame_builds,
                                retired=cache.frames_retired)
        planner = getattr(self.executor, "planner", None)
        if planner is not None:
            # Planned reads by how their statement shape was found:
            # bound from its entry, built on a first sighting, or
            # taken through the whole planner / the whole parser
            # (docs/OBSERVABILITY.md).
            shapes = dict(planner.shapes)
            shapes["full"] += pql.shape_stats["full"]
            snap["planShapes"] = shapes
        model = getattr(self.executor, "cost_model", None)
        if model is not None:
            snap["costModel"] = {"syncS": model.cal.sync_s,
                                 "hostBps": model.cal.host_bps,
                                 "hostVisitS": model.cal.host_visit_s,
                                 "uploadBps": model.cal.upload_bps,
                                 "packBps": model.cal.pack_bps,
                                 "deviceBps": model.cal.device_bps,
                                 "margin": model.margin,
                                 "drift": model.drift_snapshot()}
        # The stage clock's process totals (sched.context): self-time
        # stages of the served /query requests by lane, one tick's cost
        # of every background loop, and the last compilations by
        # program name.
        snap.update(sched_context.stage_totals())
        snap["sampledAt"] = time.time()     # two reads bound a window
        from ..parallel import mesh as mesh_mod
        snap["compileLog"] = mesh_mod.compile_log()
        return Response.json(snap)

    # -- profiling (reference handler.go:30,99 mounts net/http/pprof) --------

    def _handle_pprof_index(self, req: Request) -> Response:
        return Response(
            200, b"profile: sampled CPU profile (?seconds=N, default 5)\n"
                 b"threads: stack dump of all live threads\n"
                 b"flame: continuous-profiler folded stacks"
                 b" (?query=<id> filters to one query;"
                 b" speedscope/flamegraph.pl-loadable)\n"
                 b"heap: tracemalloc allocation sites (?n=N, default"
                 b" 30); GET is read-only, POST ?op=start|stop"
                 b" arms/disarms\n",
            "text/plain; charset=utf-8")

    def _handle_pprof_heap(self, req: Request) -> Response:
        """Read-only heap report. Arming/disarming tracemalloc mutates
        interpreter-wide state, so it lives on POST ?op=start|stop."""
        from ..utils.profiling import heap_report
        try:
            top_n = int(req.query.get("n", "30"))
        except ValueError:
            raise HTTPError(400, "invalid n")
        return Response(200,
                        heap_report(max(1, min(top_n, 500))).encode(),
                        "text/plain; charset=utf-8")

    def _handle_pprof_heap_post(self, req: Request) -> Response:
        """Arm/disarm tracemalloc: POST ?op=start | ?op=stop (the
        mutating halves of the old GET contract)."""
        from ..utils.profiling import heap_start, heap_stop
        op = req.query.get("op", "start")
        if op == "start":
            body = heap_start()
        elif op == "stop":
            body = heap_stop()
        else:
            raise HTTPError(400, f"invalid op: {op} (start|stop)")
        return Response(200, body.encode(), "text/plain; charset=utf-8")

    def _handle_pprof_flame(self, req: Request) -> Response:
        """Continuous-profiler export: collapsed-stack text aggregated
        over the bounded sample ring (load into speedscope or
        flamegraph.pl). ``?query=<id>`` filters to the samples tagged
        with that query id; ``?since=<dur>`` keeps only recent
        samples."""
        since_s = 0.0
        if req.query.get("since"):
            from ..utils.config import parse_duration
            try:
                since_s = parse_duration(req.query["since"])
            except ValueError:
                raise HTTPError(400, "invalid since")
        body = self.profiler.flame(query=req.query.get("query", ""),
                                   since_s=since_s)
        return Response(200, body.encode(), "text/plain; charset=utf-8")

    def _handle_health(self, req: Request) -> Response:
        """READINESS (not liveness): 200 only when this node can
        actually serve — holder open, gossip converged, admission not
        saturated, data dir writable. Load balancers poll this;
        /version remains the liveness probe."""
        from ..obs.slo import HealthChecker
        if self._health is None:
            self._health = HealthChecker(holder=self.holder,
                                         cluster=self.cluster,
                                         admission=self.admission,
                                         host=self.host)
        ready, checks = self._health.check()
        return Response.json(
            {"status": "ok" if ready else "unhealthy",
             "checks": checks},
            status=200 if ready else 503)

    def _handle_pprof_profile(self, req: Request) -> Response:
        from ..utils.profiling import sample_profile
        import math
        try:
            seconds = float(req.query.get("seconds", "5"))
        except ValueError:
            raise HTTPError(400, "invalid seconds")
        if not math.isfinite(seconds):
            raise HTTPError(400, "invalid seconds")
        seconds = min(max(seconds, 0.1), 120.0)
        return Response(200, sample_profile(seconds).encode(),
                        "text/plain; charset=utf-8")

    def _handle_pprof_threads(self, req: Request) -> Response:
        from ..utils.profiling import thread_dump
        return Response(200, thread_dump().encode(),
                        "text/plain; charset=utf-8")

    def _handle_get_schema(self, req: Request) -> Response:
        return Response.json({"indexes": self.holder.schema()})

    def _handle_slice_max(self, req: Request) -> Response:
        inverse = req.query.get("inverse") == "true"
        ms = (self.holder.max_inverse_slices() if inverse
              else self.holder.max_slices())
        if _PROTOBUF in req.accept:
            return Response.proto(pb.MaxSlicesResponse(MaxSlices=ms))
        return Response.json({"maxSlices": ms})

    # -- index CRUD ----------------------------------------------------------

    def _handle_get_index(self, req: Request) -> Response:
        idx = self.holder.index(req.vars["index"])
        if idx is None:
            raise HTTPError(404, "index not found")
        return Response.json({"index": {"name": idx.name}})

    @staticmethod
    def _validate_options(body: dict, valid: set[str]) -> dict:
        # handler.go:299-351: any unknown key is an error.
        for k in body:
            if k != "options":
                raise HTTPError(400, f"Unknown key: {k}")
        options = body.get("options", {})
        if not isinstance(options, dict):
            raise HTTPError(400, "options is not map")
        for k in options:
            if k not in valid:
                raise HTTPError(400, f"Unknown key: {k}:{options[k]}")
        return options

    def _handle_post_index(self, req: Request) -> Response:
        name = req.vars["index"]
        opts = self._validate_options(req.json(), _VALID_INDEX_OPTIONS)
        options = IndexOptions(
            column_label=opts.get("columnLabel", "columnID"),
            time_quantum=tq.parse_time_quantum(opts.get("timeQuantum", "")))
        validate_label(options.column_label)
        try:
            self.holder.create_index(name, options)
        except IndexExistsError as e:
            raise HTTPError(409, str(e))
        self.broadcaster.send_sync(pb.CreateIndexMessage(
            Index=name, Meta=options.encode()))
        return Response.json({})

    def _handle_delete_index(self, req: Request) -> Response:
        name = req.vars["index"]
        self.holder.delete_index(name)
        self.broadcaster.send_sync(pb.DeleteIndexMessage(Index=name))
        return Response.json({})

    def _handle_patch_index_time_quantum(self, req: Request) -> Response:
        q = tq.parse_time_quantum(req.json().get("timeQuantum", ""))
        idx = self.holder.index(req.vars["index"])
        if idx is None:
            raise HTTPError(404, "index not found")
        idx.set_time_quantum(q)
        return Response.json({})

    # -- frame CRUD ----------------------------------------------------------

    def _handle_post_frame(self, req: Request) -> Response:
        index_name, frame_name = req.vars["index"], req.vars["frame"]
        opts = self._validate_options(req.json(), _VALID_FRAME_OPTIONS)
        idx = self.holder.index(index_name)
        if idx is None:
            raise HTTPError(404, "index not found")
        options = FrameOptions(
            row_label=opts.get("rowLabel", "rowID"),
            inverse_enabled=bool(opts.get("inverseEnabled", False)),
            cache_type=opts.get("cacheType", "lru"),
            cache_size=int(opts.get("cacheSize", 50000)),
            time_quantum=tq.parse_time_quantum(opts.get("timeQuantum", "")),
            fields=self._parse_fields_option(opts.get("fields")))
        try:
            idx.create_frame(frame_name, options)
        except FrameExistsError as e:
            raise HTTPError(409, str(e))
        self.broadcaster.send_sync(pb.CreateFrameMessage(
            Index=index_name, Frame=frame_name, Meta=options.encode()))
        return Response.json({})

    def _handle_delete_frame(self, req: Request) -> Response:
        index_name, frame_name = req.vars["index"], req.vars["frame"]
        idx = self.holder.index(index_name)
        if idx is None:
            return Response.json({})
        idx.delete_frame(frame_name)
        self.broadcaster.send_sync(pb.DeleteFrameMessage(
            Index=index_name, Frame=frame_name))
        return Response.json({})

    def _handle_patch_frame_time_quantum(self, req: Request) -> Response:
        q = tq.parse_time_quantum(req.json().get("timeQuantum", ""))
        frame = self.holder.frame(req.vars["index"], req.vars["frame"])
        if frame is None:
            raise HTTPError(404, "frame not found")
        frame.set_time_quantum(q)
        return Response.json({})

    def _handle_get_frame_views(self, req: Request) -> Response:
        frame = self.holder.frame(req.vars["index"], req.vars["frame"])
        if frame is None:
            raise HTTPError(404, "frame not found")
        return Response.json({"views": sorted(frame.views)})

    # -- BSI integer fields --------------------------------------------------

    @staticmethod
    def _parse_fields_option(raw) -> Optional[list[Field]]:
        if raw is None:
            return None
        if not isinstance(raw, list):
            raise HTTPError(400, "fields is not a list")
        out = []
        for o in raw:
            if not isinstance(o, dict) or "name" not in o:
                raise HTTPError(400, f"invalid field: {o!r}")
            for k in o:
                if k not in ("name", "min", "max"):
                    raise HTTPError(400, f"Unknown key: {k}:{o[k]}")
            try:
                out.append(Field(name=o["name"],
                                 min=int(o.get("min", 0)),
                                 max=int(o.get("max", 0))))
            except (TypeError, ValueError) as e:
                raise HTTPError(400, str(e))
        return out

    def _handle_get_frame_fields(self, req: Request) -> Response:
        frame = self.holder.frame(req.vars["index"], req.vars["frame"])
        if frame is None:
            raise HTTPError(404, "frame not found")
        return Response.json(
            {"fields": [f.to_json() for f in frame.fields()]})

    def _handle_post_frame_field(self, req: Request) -> Response:
        """Create one BSI field on an existing frame (body:
        {"min": N, "max": M}); re-broadcasts the frame meta so peers
        register it too."""
        index_name, frame_name = req.vars["index"], req.vars["frame"]
        frame = self.holder.frame(index_name, frame_name)
        if frame is None:
            raise HTTPError(404, "frame not found")
        body = req.json()
        for k in body:
            if k not in ("min", "max"):
                raise HTTPError(400, f"Unknown key: {k}:{body[k]}")
        try:
            field = Field(name=req.vars["field"],
                          min=int(body.get("min", 0)),
                          max=int(body.get("max", 0)))
        except (TypeError, ValueError) as e:
            raise HTTPError(400, str(e))
        frame.create_field(field)
        self.broadcaster.send_sync(pb.CreateFrameMessage(
            Index=index_name, Frame=frame_name,
            Meta=frame.options.encode()))
        return Response.json({})

    def _handle_post_field_import(self, req: Request) -> Response:
        """Bulk field-value import: protobuf ImportValueRequest (one
        slice, owner-checked like /import) or the JSON convenience
        form {"columns": [...], "values": [...]}. The JSON form
        requires EVERY touched slice to be owned by this host (412
        otherwise, nothing applied) — clients spanning owners must
        split per slice like cluster.client.import_field_values."""
        index_name, frame_name = req.vars["index"], req.vars["frame"]
        field_name = req.vars["field"]
        if req.content_type == _PROTOBUF:
            ireq = pb.ImportValueRequest.FromString(req.body())
            if (ireq.Index, ireq.Frame, ireq.Field) != (
                    index_name, frame_name, field_name):
                raise HTTPError(400, "import target mismatch")
            cols = np.fromiter(ireq.ColumnIDs, np.uint64,
                               len(ireq.ColumnIDs))
            vals = np.fromiter(ireq.Values, np.int64, len(ireq.Values))
            if self.cluster is not None and not self.cluster.owns_fragment(
                    self.host, index_name, ireq.Slice):
                raise HTTPError(412, f"host does not own slice"
                                     f" {self.host}-{index_name}"
                                     f" slice:{ireq.Slice}")
        else:
            body = req.json()
            cols = np.asarray(body.get("columns", []), dtype=np.uint64)
            vals = np.asarray(body.get("values", []), dtype=np.int64)
            if self.cluster is not None and len(cols):
                from .. import SLICE_WIDTH
                for slice in np.unique(cols // np.uint64(
                        SLICE_WIDTH)).tolist():
                    if not self.cluster.owns_fragment(
                            self.host, index_name, slice):
                        raise HTTPError(
                            412, f"host does not own slice"
                                 f" {self.host}-{index_name}"
                                 f" slice:{slice}")
        if len(cols) != len(vals):
            raise HTTPError(400, "import array length mismatch")
        frame = self.holder.frame(index_name, frame_name)
        if frame is None:
            raise HTTPError(404, "frame not found")
        if frame.field(field_name) is None:
            raise HTTPError(404, "field not found")
        frame.import_field_values(field_name, cols, vals)
        storage_wal.barrier_all()  # commit before the 200
        obs_metrics.IMPORT_BITS.labels("field_values").inc(len(cols))
        if req.content_type == _PROTOBUF:
            return Response.proto(pb.ImportResponse())
        return Response.json({})

    # -- query lifecycle (sched subsystem; docs/SCHEDULING.md) ---------------

    def _query_timeout_s(self, req: Request) -> Optional[float]:
        """Deadline budget for this request: ``X-Pilosa-Deadline``
        (remaining seconds — the cluster fan-out form, so a peer
        inherits what is LEFT of the coordinator's budget) wins over
        ``?timeout=`` (Go-style duration, the client-facing form),
        which wins over the configured default. None = unbounded."""
        hdr = self.environ_header(req, "HTTP_X_PILOSA_DEADLINE")
        if hdr:
            try:
                return max(float(hdr), 0.001)
            except ValueError:
                raise HTTPError(400, f"invalid X-Pilosa-Deadline: {hdr}")
        arg = req.query.get("timeout")
        if arg:
            from ..utils.config import parse_duration
            try:
                return max(parse_duration(arg), 0.001)
            except ValueError:
                raise HTTPError(400, f"invalid timeout: {arg}")
        return self.default_timeout_s or None

    @staticmethod
    def environ_header(req: Request, key: str) -> str:
        return req.environ.get(key, "")

    def _check_writable(self, lane: str) -> None:
        """Disk-full graceful degradation (fault.diskfull): while the
        node is write-unready after ENOSPC, writes answer 507 +
        Retry-After INSTEAD of being admitted into a doomed WAL
        append — reads and admin keep serving. The throttled probe
        inside write_ready() is also the auto-recovery path."""
        if lane != LANE_WRITE:
            return
        if fault_diskfull.write_ready():
            return
        st = fault_diskfull.default()
        raise HTTPError(
            507, "insufficient storage: node is write-unready after"
                 " ENOSPC (reads still serving; retry after space"
                 " frees)",
            headers=[("Retry-After", str(st.retry_after_s()))])

    def _admit(self, lane: str, ctx=None, tenant: str = ""):
        """Acquire an execution slot (None admission = unlimited, for
        bare test handlers). AdmissionFullError maps to 429 with the
        controller's Retry-After estimate — computed per lane, and per
        tenant-lane when the rejection was the tenant's own quota; a
        deadline that expires while QUEUED maps like any other expiry
        (504) — the query never occupied a slot."""
        self._check_writable(lane)
        if self.admission is None:
            return None
        try:
            return self.admission.acquire(lane, ctx,
                                          tenant=tenant or None)
        except AdmissionFullError as e:
            if self.stats is not None:
                self.stats.count("queriesRejected", 1)
            obs_metrics.ADMISSION_REJECTED.labels(lane).inc()
            if e.tenant:
                # Tenant-scoped shed: only the offending tenant 429s,
                # and its chargeback row says so. note_shed owns the
                # TENANT_SHED increment (one site, metric + registry
                # counter in lockstep); the direct inc covers bare
                # handlers with no registry.
                if self.tenants is not None:
                    self.tenants.note_shed(e.tenant, lane)
                else:
                    obs_metrics.TENANT_SHED.labels(e.tenant,
                                                   lane).inc()
            raise HTTPError(
                429, f"too many requests: {e}",
                headers=[("Retry-After",
                          str(int(e.retry_after_s)))])

    @contextlib.contextmanager
    def _admitted(self, lane: str, tenant: str = ""):
        """Slot-scoped admission for the non-query lanes (imports ride
        ``write``, schema mutations ``admin``), under the resolved
        tenant principal."""
        slot = self._admit(lane, tenant=tenant)
        try:
            yield
        finally:
            if slot is not None:
                slot.release()

    def _handle_debug_queries(self, req: Request) -> Response:
        out = {"queries": self.registry.active(),
               "slow": self.registry.slow_queries()}
        if self.admission is not None:
            out["admission"] = self.admission.snapshot()
        return Response.json(out)

    def _handle_debug_tenants(self, req: Request) -> Response:
        """The multi-tenant operator view (sched.tenants): per tenant
        — policy + effective weight, penalty-box state, in-flight /
        queued / served / shed / killed, cache residency, and the
        latest SLO burn rates. One merged row per tenant; the
        ``writeReady`` block rides along since a write-unready node
        sheds every tenant's writes at once."""
        rows: dict[str, dict] = {}

        def row(name: str) -> dict:
            return rows.setdefault(name, {})

        if self.tenants is not None:
            for name, snap in self.tenants.snapshot().items():
                row(name).update(snap)
        if self.admission is not None:
            adm = self.admission.snapshot()
            for name, snap in (adm.get("tenants") or {}).items():
                row(name).update(snap)
        if self.tenants is not None:
            # Unknown-but-active tenants (indexes with no [tenants.*]
            # entry) ride the default policy — their rows say which
            # policy actually governs them instead of showing nothing.
            for name, r in rows.items():
                if "policy" not in r:
                    score = self.tenants.penalty_score(name)
                    r["policy"] = self.tenants.policy(name).to_json()
                    r["effectiveWeight"] = round(
                        self.tenants.effective_weight(name), 4)
                    r["penaltyScore"] = round(score, 4)
                    r["inPenaltyBox"] = score > 0.0
                    r.setdefault("killed", 0)
                    r.setdefault("shed", 0)
        usage_fn = getattr(self.executor, "tenant_cache_usage", None)
        if callable(usage_fn):
            for name, snap in usage_fn().items():
                row(name)["cache"] = snap
        if self.tenant_slo is not None:
            for name, snap in self.tenant_slo.last().items():
                row(name)["slo"] = snap
        return Response.json({
            "tenants": rows,
            "writeReady": fault_diskfull.default().snapshot(),
        })

    def _handle_delete_query(self, req: Request) -> Response:
        """Cancel one query CLUSTER-WIDE: flip the local cancel flag
        (every executor layer checks it cooperatively) and broadcast a
        CancelQueryMessage so peers cancel the legs registered under
        the same id. ``?local=true`` limits to this node (the form the
        broadcast receiver itself applies, and a debugging escape
        hatch)."""
        qid = req.vars["qid"]
        n = self.registry.cancel_local(qid)
        if req.query.get("local") != "true":
            try:
                self.broadcaster.send_async(CancelQueryMessage(qid))
            except Exception as e:  # noqa: BLE001 - best-effort fan-out
                self.logger.printf("cancel broadcast failed: %s", e)
        return Response.json({"id": qid, "cancelled": n})

    # -- observability (obs subsystem; docs/OBSERVABILITY.md) ----------------

    def _handle_debug_slow_queries(self, req: Request) -> Response:
        """The slow-query log over HTTP: recent entries with per-stage
        timings and the query/trace id (PR 2's log was stderr-only —
        unusable without grepping server logs)."""
        return Response.json({"slow": self.registry.slow_queries()})

    def _handle_metrics(self, req: Request) -> Response:
        """Prometheus text exposition of the process registry. Only
        the CHEAP admission gauges refresh at scrape time; the heavy
        samplers (the O(fragments) holder walk, compile/residency
        snapshots) stay on the runtime collector's background cadence
        — a scrape must not get slower as the index grows."""
        # Content negotiation: an OpenMetrics scraper gets exemplars
        # (the trace/query id riding each latency bucket); everyone
        # else keeps the plain 0.0.4 exposition byte-for-byte (the
        # same body the federation legs scrape — one implementation).
        if "application/openmetrics-text" in req.accept:
            self._refresh_scrape_gauges()
            body = obs_metrics.default_registry().render(
                openmetrics=True).encode()
            return Response(
                200, body,
                "application/openmetrics-text; version=1.0.0;"
                " charset=utf-8")
        return Response(200, self._local_metrics_text().encode(),
                        "text/plain; version=0.0.4; charset=utf-8")

    # -- fleet observability (obs.federate / obs.history / obs.sentinel) -----

    def _refresh_scrape_gauges(self) -> None:
        """Only the CHEAP admission gauges refresh at scrape time; the
        heavy samplers stay on the runtime collector's cadence."""
        if self.admission is not None:
            adm = self.admission.snapshot()
            obs_metrics.ADMISSION_IN_FLIGHT.set(adm.get("inFlight", 0))
            for lane, depth in (adm.get("queued") or {}).items():
                obs_metrics.ADMISSION_QUEUE_DEPTH.labels(lane).set(
                    depth)
            tif = getattr(self.admission, "tenant_in_flight", None)
            if callable(tif):
                now = tif()
                # Zero stale children first: the controller pops a
                # tenant's key when its count drains, so a gauge set
                # only from present keys would report the last busy
                # value forever.
                for labels, _child in \
                        obs_metrics.TENANT_INFLIGHT._label_dicts():
                    t = labels.get("tenant", "")
                    if t and t not in now:
                        obs_metrics.TENANT_INFLIGHT.labels(t).set(0)
                for tenant, n in now.items():
                    obs_metrics.TENANT_INFLIGHT.labels(tenant).set(n)
        usage_fn = getattr(self.executor, "tenant_cache_usage", None)
        if callable(usage_fn):
            usage = usage_fn()
            for labels, _child in \
                    obs_metrics.TENANT_CACHE_BYTES._label_dicts():
                t = labels.get("tenant", "")
                if t and t not in usage:
                    obs_metrics.TENANT_CACHE_BYTES.labels(t).set(0)
            for tenant, ent in usage.items():
                obs_metrics.TENANT_CACHE_BYTES.labels(tenant).set(
                    ent.get("bytes", 0))

    def _local_metrics_text(self) -> str:
        """The local 0.0.4 exposition exactly as /metrics serves it —
        also the body the /metrics/cluster local leg merges."""
        self._refresh_scrape_gauges()
        return obs_metrics.default_registry().render()

    def _partial_or_503(self, req: Request, missing: list[str],
                        headers: list) -> None:
        """The federation partial contract (docs/OBSERVABILITY.md):
        unreachable peers fail the request unless ``?partial=1``, in
        which case the merged answer is served and the missing nodes
        ride ``X-Pilosa-Partial-Nodes``."""
        if not missing:
            return
        if req.query.get("partial") != "1":
            raise HTTPError(
                503, "federation incomplete; unreachable nodes: "
                     + ",".join(missing)
                     + " (retry with ?partial=1 for a marked partial"
                       " rollup)")
        headers.append(("X-Pilosa-Partial-Nodes", ",".join(missing)))

    def _handle_metrics_cluster(self, req: Request) -> Response:
        """Cluster-wide Prometheus exposition: ONE bounded parallel
        scrape of every peer's /metrics (pooled clients — an open
        breaker fails a dead peer's leg fast), merged at query time:
        counters sum, histograms merge, gauges stay per-node labeled
        ``{node}``. The Monarch shape: history lives at the leaf,
        aggregation happens when the question is asked."""
        from ..obs import federate as obs_federate
        fed = self.federator

        def fetch(host: str) -> dict:
            client = fed.client_for(host)
            return obs_federate.parse_exposition(
                client.metrics_text(host=host,
                                    deadline_s=fed.peer_timeout_s))

        results, missing = fed.fan_out(
            fetch,
            lambda: obs_federate.parse_exposition(
                self._local_metrics_text()))
        headers: list = []
        self._partial_or_503(req, missing, headers)
        body = obs_federate.render_merged(
            obs_federate.merge_node_families(results)).encode()
        headers.append(("X-Pilosa-Federated-Nodes",
                        str(len(results))))
        return Response(200, body,
                        "text/plain; version=0.0.4; charset=utf-8",
                        headers=headers)

    def _handle_debug_cluster(self, req: Request) -> Response:
        """The fleet rollup: every node's local debug block (build
        info, placement epoch, breaker states, SLO burn, WAL flusher
        health, resize phase — the blackbox state, fleet-wide), plus
        a version-skew verdict. ``?local=1`` answers just this node's
        block (the internal leg the coordinator fans out)."""
        state_fn = getattr(self.status_handler, "local_debug_state",
                           None)

        def local() -> dict:
            if state_fn is not None:
                return state_fn()
            from ..obs.runtime import build_info
            return {"host": self.host, "build": build_info()}

        if req.query.get("local") == "1":
            return Response.json(local())
        fed = self.federator

        def fetch(host: str) -> dict:
            client = fed.client_for(host)
            return client.debug_cluster_local(
                host=host, deadline_s=fed.peer_timeout_s)

        results, missing = fed.fan_out(fetch, local)
        headers: list = []
        self._partial_or_503(req, missing, headers)
        versions: dict[str, str] = {}
        for host, block in results.items():
            versions[host] = str(
                (block.get("build") or {}).get("version", ""))
        # Gossip-learned builds cover nodes a scrape can't reach (the
        # rolling-restart window where skew matters most).
        local_block = results.get(self.host) or {}
        for host, build in (local_block.get("gossipBuilds")
                            or {}).items():
            versions.setdefault(host, str(build.get("version", "")))
        distinct = {v for v in versions.values() if v}
        return Response.json(
            {"coordinator": self.host,
             "nodes": results,
             "missing": missing,
             "versions": versions,
             "versionSkew": len(distinct) > 1},
            headers=headers)

    def _handle_metrics_history(self, req: Request) -> Response:
        """The on-disk metric history (obs.history) as JSON series:
        ``?family=`` selects a family (and its derived ``:p50``/
        ``:p99``/``:rate`` forms), ``?label=k=v[,k=v]`` filters,
        ``?window=``/``?step=`` pick the trailing window and
        resolution hint. ``?scope=cluster`` asks every node the same
        question and returns the series with per-node attribution."""
        from ..utils.config import parse_duration
        family = req.query.get("family", "")
        window_s, step_s = 3600.0, 0.0
        try:
            if req.query.get("window"):
                window_s = parse_duration(req.query["window"])
            if req.query.get("step"):
                step_s = parse_duration(req.query["step"])
        except ValueError:
            raise HTTPError(400, "invalid window/step")
        label_filter: dict = {}
        for pair in (req.query.get("label") or "").split(","):
            if not pair:
                continue
            k, sep, v = pair.partition("=")
            if not sep:
                raise HTTPError(400, f"invalid label filter: {pair!r}")
            label_filter[k] = v

        def local() -> dict:
            if self.history is None:
                return {"family": family, "series": [],
                        "enabled": False}
            return self.history.series(
                family, label_filter or None, window_s, step_s)

        if req.query.get("scope") != "cluster":
            out = local()
            out.setdefault("enabled", self.history is not None)
            return Response.json(out)
        fed = self.federator

        def fetch(host: str) -> dict:
            client = fed.client_for(host)
            return client.metrics_history(
                family=family, label=req.query.get("label", ""),
                window=req.query.get("window", ""),
                step=req.query.get("step", ""), host=host,
                deadline_s=fed.peer_timeout_s)

        results, missing = fed.fan_out(fetch, local)
        headers: list = []
        self._partial_or_503(req, missing, headers)
        series = []
        for host in sorted(results):
            for s in results[host].get("series") or []:
                series.append({**s, "node": host})
        return Response.json(
            {"family": family, "scope": "cluster",
             "windowS": window_s, "missing": missing,
             "series": series},
            headers=headers)

    def _handle_debug_capture(self, req: Request) -> Response:
        """Workload-capture status (obs.capture): mode, sampling,
        redaction policy, cursor, and the ring's byte accounting. A
        handler without a capture store answers disabled."""
        cap = self.capture
        if cap is None:
            return Response.json({"enabled": False, "mode": "off"})
        out = cap.status()
        out["enabled"] = cap.enabled
        return Response.json(out)

    def _handle_debug_capture_records(self, req: Request) -> Response:
        """Paged capture export: ``?since=<seq>`` (exclusive cursor)
        + ``?limit=`` pages the local ring oldest-first; the next
        page's cursor is the returned ``next``. ``?scope=cluster``
        fans out to every node and merges the streams by arrival
        wall-clock (obs.capture.merge_streams) — the merged form
        ``pilosa-tpu replay`` re-issues."""
        try:
            since = int(req.query.get("since", "0"))
            limit = int(req.query.get("limit", "500"))
        except ValueError:
            raise HTTPError(400, "invalid since/limit")

        def local() -> dict:
            cap = self.capture
            recs = cap.export(since=since, limit=limit) \
                if cap is not None else []
            return {"node": self.host, "records": recs,
                    "next": recs[-1]["seq"] if recs else since}

        if req.query.get("scope") != "cluster":
            return Response.json(local())
        fed = self.federator

        def fetch(host: str) -> dict:
            client = fed.client_for(host)
            return client.capture_records(
                since=since, limit=limit, host=host,
                deadline_s=fed.peer_timeout_s)

        results, missing = fed.fan_out(fetch, local)
        headers: list = []
        self._partial_or_503(req, missing, headers)
        merged = obs_capture.merge_streams(
            [r.get("records") or [] for r in results.values()])
        return Response.json(
            {"scope": "cluster", "records": merged,
             "nodes": sorted(results), "missing": missing},
            headers=headers)

    def _handle_debug_plans(self, req: Request) -> Response:
        """The bounded per-fingerprint plan store (plan.store): hit
        counts, latency p50/p99, est-vs-actual drift, and the last
        observed plan per normalized query shape; ``?limit=N`` bounds
        the listing (hottest fingerprints first). The planner's own
        state (decision totals, subresult cache) rides along."""
        try:
            limit = max(1, int(req.query.get("limit", "64")))
        except ValueError:
            raise HTTPError(400, "invalid limit")
        out = {"enabled": plan_record.enabled()}
        ex = self.executor
        store = getattr(ex, "plan_store", None)
        if store is not None:
            out.update(store.snapshot(limit=limit))
        planner = getattr(ex, "planner", None)
        if planner is not None:
            out["planner"] = planner.snapshot()
        return Response.json(out)

    def _handle_debug_sentinel(self, req: Request) -> Response:
        """The regression sentinel's state: recent findings, active
        conditions, and the rule thresholds (obs.sentinel)."""
        out: dict = {"enabled": self.sentinel is not None}
        if self.sentinel is not None:
            out.update(self.sentinel.snapshot())
        return Response.json(out)

    def _handle_debug_traces(self, req: Request) -> Response:
        """The in-memory ring by default; ``?source=disk`` lists the
        PERSISTED kept traces (tail sampler's segment ring — survives
        restarts), ``?reason=<keep-reason>`` filters either source,
        ``?limit=N&offset=M`` page through the listing (newest first,
        default limit 100) — so the disk ring is browsable without
        streaming every kept trace."""
        from ..obs import sampler as obs_sampler
        reason = req.query.get("reason", "")
        try:
            limit = max(1, int(req.query.get("limit", "100")))
            offset = max(0, int(req.query.get("offset", "0")))
        except ValueError:
            raise HTTPError(400, "invalid limit/offset")
        if req.query.get("source") == "disk":
            disk = self.sampler.disk if self.sampler is not None \
                else None
            traces: list[dict] = []
            matched = 0
            if disk is not None:
                for record in disk.scan():
                    if reason and record.get("reason") != reason:
                        continue
                    matched += 1
                    if matched <= offset:
                        continue
                    if len(traces) < limit:
                        traces.append(
                            obs_sampler.record_summary(record))
                        # Keep counting past the page: ``total`` tells
                        # the pager whether another page exists.
            out = {"enabled": self.tracer.enabled, "source": "disk",
                   "traces": traces, "offset": offset, "limit": limit,
                   "total": matched}
            if disk is not None:
                out["disk"] = disk.stats()
            return Response.json(out)
        traces = self.tracer.traces()
        if reason:
            traces = [t for t in traces if t.get("reason") == reason]
        return Response.json({"enabled": self.tracer.enabled,
                              "tail": self.sampler is not None,
                              "offset": offset, "limit": limit,
                              "total": len(traces),
                              "traces": traces[offset:offset + limit]})

    def _handle_debug_traces_summary(self, req: Request) -> Response:
        """Keep-reason roll-up over both stores: how many kept traces
        per reason in the in-memory ring and the on-disk segment ring
        — the browse-entry point before paging /debug/traces."""
        ring: dict[str, int] = {}
        for t in self.tracer.traces():
            r = t.get("reason") or "unkept"
            ring[r] = ring.get(r, 0) + 1
        disk_counts: dict[str, int] = {}
        out: dict = {"ring": ring, "disk": disk_counts}
        disk = self.sampler.disk if self.sampler is not None else None
        if disk is not None:
            for record in disk.scan():
                r = str(record.get("reason") or "unknown")
                disk_counts[r] = disk_counts.get(r, 0) + 1
            out["diskStats"] = disk.stats()
        return Response.json(out)

    # -- failpoint admin (fault subsystem; docs/FAULT_TOLERANCE.md) ----------

    def _handle_debug_failpoints(self, req: Request) -> Response:
        """The armed-failpoint schedule + the seed that replays it."""
        from ..fault import failpoints as fp
        return Response.json(fp.default().snapshot())

    def _handle_post_failpoints(self, req: Request) -> Response:
        """Arm/disarm failpoints at runtime. Body forms:
        ``{"site": "rpc.send", "spec": "error(0.5)"}`` or the bulk
        ``{"failpoints": {"rpc.send": "error", "wal.append": "off"}}``.
        Spec "off" disarms; an unknown site or malformed spec is 400
        with nothing armed."""
        from ..fault import failpoints as fp
        body = req.json()
        updates: dict = {}
        if "failpoints" in body:
            if not isinstance(body["failpoints"], dict):
                raise HTTPError(400, "failpoints is not a map")
            updates.update(body["failpoints"])
        if "site" in body:
            updates[body["site"]] = body.get("spec", "off")
        if not updates:
            raise HTTPError(400, "no failpoints given")
        reg = fp.default()
        # Validate everything before arming anything: a bulk update
        # must not half-apply.
        for site, spec in updates.items():
            if site not in fp.SITES:
                raise HTTPError(400, f"unknown failpoint site: {site}")
            try:
                fp.parse_spec(site, str(spec))
            except ValueError as e:
                raise HTTPError(400, str(e))
        for site, spec in updates.items():
            reg.arm(site, str(spec))
            self.logger.printf("failpoint %s: %s (seed %d)", site,
                               spec or "off", reg.seed)
        return Response.json(reg.snapshot())

    def _handle_debug_integrity(self, req: Request) -> Response:
        """Storage-integrity state: quarantined fragments (what, why,
        since when), scrub pass progress/totals, repair totals, and
        the per-fragment footer coverage summary (how much of the
        fleet's bytes actually carry checksums — vintage files read
        fine but scrub blind)."""
        covered = vintage = 0
        iter_fragments = getattr(self.holder, "iter_fragments", None)
        for frag in (iter_fragments() if iter_fragments else ()):
            storage = getattr(frag, "storage", None)
            if storage is not None and getattr(storage, "footer",
                                               None) is not None:
                covered += 1
            else:
                vintage += 1
        registry = getattr(self.holder, "quarantine", None)
        out: dict = {
            "quarantined": registry.entries() if registry is not None
            else [],
            "coverage": {"footered": covered, "vintage": vintage}}
        if self.scrubber is not None:
            out["scrub"] = self.scrubber.state()
        if self.repairer is not None:
            out["repair"] = self.repairer.state()
        return Response.json(out)

    def _handle_debug_tier(self, req: Request) -> Response:
        """Tiered-storage state (pilosa_tpu.tier): per-tier fragment
        and byte counts, resident bytes vs budget/watermarks,
        per-tenant residency, transition totals, blocked cold fetches,
        and the blob store summary. ``?entries=1`` appends the
        per-fragment ledger (optionally filtered ``&tier=cold``);
        ``?pass=1`` runs one manager pass inline and includes its
        summary (operator spot checks, chaos tests)."""
        if self.tier is None:
            return Response.json({"enabled": False})
        out = self.tier.state()
        if req.query.get("pass") == "1":
            out["pass"] = self.tier.pass_once()
        if req.query.get("entries") == "1":
            out["entries"] = self.tier.entries(
                req.query.get("tier", ""))[:1024]
        return Response.json(out)

    def _handle_post_integrity_scrub(self, req: Request) -> Response:
        """Trigger an immediate scrub pass. ``?sync=1`` runs the pass
        inline and returns its summary (operator spot checks, chaos
        tests); the default just wakes the background thread."""
        if self.scrubber is None:
            raise HTTPError(503, "no scrubber on this node")
        if req.query.get("sync") == "1":
            return Response.json(self.scrubber.pass_once())
        self.scrubber.trigger()
        return Response.json({"triggered": True})

    def _handle_debug_trace(self, req: Request) -> Response:
        """One trace as Chrome trace-event JSON (open in perfetto);
        ``?format=spans`` returns the raw span list instead. A miss in
        the in-memory ring falls back to the tail sampler's disk ring
        (``?source=disk`` skips the ring and goes straight there), so
        a persisted trace stays addressable after a restart."""
        trace = None
        if req.query.get("source") != "disk":
            trace = self.tracer.get(req.vars["qid"])
        if trace is None and self.sampler is not None \
                and self.sampler.disk is not None:
            from ..obs import sampler as obs_sampler
            qid = req.vars["qid"]
            for record in self.sampler.disk.scan():
                if record.get("id") == qid:
                    trace = obs_sampler.record_to_trace(record)
                    break
        if trace is None:
            raise HTTPError(404, "trace not found")
        if req.query.get("format") == "spans":
            return Response.json(
                {"id": trace.id, "reason": trace.keep_reason,
                 "spans": [s.to_json() for s in trace.spans()]})
        return Response.json(trace.to_chrome())

    def _handle_debug_blackbox(self, req: Request) -> Response:
        """Flight-recorder state: ring/dump stats plus the most recent
        snapshots (``?limit=N``, default 8) and the watchdog's trip
        record — the read side of docs/OBSERVABILITY.md's blackbox."""
        try:
            limit = max(0, int(req.query.get("limit", "8")))
        except ValueError:
            raise HTTPError(400, "invalid limit")
        out: dict = {"enabled": self.blackbox is not None}
        if self.blackbox is not None:
            out.update(self.blackbox.stats())
            snaps = []
            if limit:
                for rec in self.blackbox.ring.scan():
                    snaps.append(rec)
                    if len(snaps) >= limit:
                        break
            out["recent"] = snaps
        if self.watchdog is not None:
            out["watchdog"] = self.watchdog.snapshot()
        return Response.json(out)

    def _handle_post_blackbox_dump(self, req: Request) -> Response:
        """Force a full flight-recorder dump (cause ``api``) — the
        operator's "capture everything NOW" button."""
        if self.blackbox is None:
            raise HTTPError(404, "no blackbox recorder")
        path = self.blackbox.dump("api")
        if path is None:
            raise HTTPError(500, "blackbox dump failed")
        return Response.json({"dumped": path})

    # -- query ---------------------------------------------------------------

    def _handle_post_query(self, req: Request) -> Response:
        # The first read freezes the initial load (sched.context).
        sched_context.settle_first_read()
        clock = req.environ.get(sched_context.CLOCK_ENVIRON)
        if clock is not None:
            return self._post_query(req, clock)
        # A bare WSGI caller, no front end that times its own read and
        # write: the query's stages begin and end here.
        clock = sched_context.StageClock()
        try:
            return self._post_query(req, clock)
        finally:
            clock.close()

    def _post_query(self, req: Request,
                    clock: sched_context.StageClock) -> Response:
        """One query under its stage clock (sched.context): this
        thread's top-level stages are switched here in order — parse,
        setup (context, accounting, tracer, registry), finish (the
        ``finally`` below), encode — with admission, execute and commit
        nested inside setup, so the clock never stands on no stage."""
        index_name = req.vars["index"]
        proto_out = _PROTOBUF in req.accept

        def error_resp(status, msg, headers=None):
            if proto_out:
                return Response.proto(pb.QueryResponse(Err=msg), status,
                                      headers=headers)
            return Response.json({"error": msg}, status,
                                 headers=headers)

        # Read request (handler.go:811-870).
        if req.content_type == _PROTOBUF:
            preq = pb.QueryRequest.FromString(req.body())
            query_str = preq.Query
            slices = list(preq.Slices)
            column_attrs = preq.ColumnAttrs
            remote = preq.Remote
        else:
            query_str = req.body().decode()
            try:
                slices = [int(s)
                          for s in req.query.get("slices", "").split(",")
                          if s != ""]
            except ValueError:
                return error_resp(400, "invalid slice argument")
            column_attrs = req.query.get("columnAttrs") == "true"
            remote = False

        clock.switch("parse")
        try:
            query = pql.parse(query_str)
        except PilosaError as e:
            return error_resp(400, str(e))
        clock.switch("setup")

        if req.query.get("plan") == "1" and not remote:
            # EXPLAIN-only: plan the query without executing. The
            # response mirrors ?profile=1's plan block with empty
            # results — estimates and decisions but no actuals.
            try:
                tree = self.executor.explain(index_name, query,
                                             slices or None)
            except PilosaError as e:
                return error_resp(400, str(e))
            return Response.json({"results": [], "plan": tree})

        # Lifecycle: classify the lane, build the QueryContext (remote
        # legs inherit the coordinator's id + remaining budget via
        # headers), admit, register for /debug/queries visibility.
        from ..executor import _WRITE_CALLS, ExecOptions
        lane = (LANE_WRITE
                if any(c.name in _WRITE_CALLS for c in query.calls)
                else LANE_READ)
        # Tenant principal (sched.tenants): the X-Pilosa-Tenant header
        # on forwarded legs (the coordinator's principal), the index
        # otherwise — resolved BEFORE admission so the stride/quota
        # accounting and the 429 counters charge the right tenant.
        tenant = (self.environ_header(req, "HTTP_X_PILOSA_TENANT")
                  or index_name)
        ctx = QueryContext(
            pql=query_str, index=index_name, lane=lane,
            timeout_s=self._query_timeout_s(req),
            id=self.environ_header(req, "HTTP_X_PILOSA_QUERY_ID") or None,
            remote=remote, node=self.host, tenant=tenant, clock=clock)
        # ?profile=1 asks for EXPLAIN ANALYZE: the executor fills in
        # exact per-node actual cardinalities (it pays one count()
        # walk per planned call) on top of the always-on wall times.
        ctx.profile = req.query.get("profile") == "1"
        # Resource accounting (obs.accounting): every query gets a cost
        # ledger — container ops by kind, device bytes, compile ms, RPC
        # bytes — unless accounting is switched off. Remote legs keep
        # their own ledger AND piggyback it back for stitching.
        if self.accounting:
            obs_accounting.attach(ctx, node=self.host)
        # Slow-query kill policy: when this tenant's policy has cost
        # ceilings, every ctx.check() (the stage boundaries, on EVERY
        # node this query touches) compares the live ledger against
        # them — a breach kills cluster-wide via the cancel broadcast.
        if self.tenants is not None:
            self.tenants.install(ctx)
        # Distributed tracing (obs.trace): traced when this node's
        # tracer is on, the request opts in (?trace=1), or a
        # coordinator asked this forwarded leg to trace itself
        # (X-Pilosa-Trace) — remote legs piggyback their spans back on
        # the response for stitching. With tail sampling wired
        # (obs.sampler — the server default), EVERY query buffers
        # spans and the keep decision runs at query end instead.
        trace = None
        trace_requested = (
            self.tracer.enabled or req.query.get("trace") == "1"
            or (remote and self.environ_header(
                req, "HTTP_X_PILOSA_TRACE") == "1"))
        if trace_requested or self.sampler is not None:
            trace = self.tracer.start(ctx, node=self.host)
        # Query latency label set: one call name when the query is
        # homogeneous, "multi" otherwise (bounded cardinality).
        call_names = {c.name for c in query.calls}
        call_label = call_names.pop() if len(call_names) == 1 else "multi"

        def _resp_headers() -> list:
            # The id rides every response; a traced REMOTE leg also
            # piggybacks its spans — on error responses too, since a
            # failing leg is exactly the one the coordinator's
            # stitched trace must not be missing. The cost ledger rides
            # the same way: a compact roll-up on EVERY response
            # (X-Pilosa-Stats) and, on remote legs, the full per-node
            # tree (X-Pilosa-Cost) for the coordinator to stitch.
            hs = [("X-Pilosa-Query-Id", ctx.id)]
            if remote:
                # Generation tokens ride every internal leg's response
                # (cluster.generations): the coordinator's map learns
                # this node's current per-fragment (uid, generation)
                # state for the served slices — the fact its remote
                # result-cache keys validate against.
                gh = self._generations_header(index_name, slices)
                if gh is not None:
                    hs.append(gh)
            if trace is not None and remote:
                hs.append((obs_trace.SPANS_HEADER, trace.spans_json()))
            if ctx.cost is not None:
                hs.append((obs_accounting.STATS_HEADER,
                           json.dumps(ctx.cost.summary(),
                                      separators=(",", ":"))))
                if remote:
                    hs.append((obs_accounting.COST_HEADER,
                               ctx.cost.wire_json(dict(ctx.stages))))
            if ctx.plan is not None and remote:
                # Remote legs piggyback their plan for the
                # coordinator to stitch (the cost-tree contract).
                hs.append((plan_record.PLAN_HEADER,
                           ctx.plan.wire_json()))
            if ctx.result_digest:
                # The canonical result digest (obs.capture): set at
                # query end on success, so error responses (digest
                # would be meaningless) skip the header.
                hs.append((obs_capture.DIGEST_HEADER,
                           ctx.result_digest))
            return hs
        # Register BEFORE admission so queued queries are visible at
        # /debug/queries and cancellable while they wait (a DELETE or
        # an expiring deadline dequeues them without ever holding a
        # slot). Forwarded legs were admitted once at their
        # coordinator; re-admitting them here could deadlock a
        # saturated cluster (every node holding a slot while waiting
        # on a peer's slot).
        slot = None
        err: Optional[BaseException] = None
        exec_opt = None
        self.registry.register(ctx)
        try:
            if not remote:
                with ctx.stage("admission"):
                    slot = self._admit(lane, ctx)
            ctx.state = "running"
            # Degraded reads (?partial=1, fault subsystem): slices
            # with no reachable replica are skipped and reported in
            # X-Pilosa-Partial instead of failing the whole query.
            # Coordinator-only: a forwarded leg answers strictly so
            # its coordinator decides the degradation policy.
            exec_opt = ExecOptions(
                remote=remote,
                pod_local=req.query.get("podLocal") == "true",
                ctx=ctx,
                partial=(req.query.get("partial") == "1"
                         and not remote),
                missing_slices=[])
            with ctx.stage("execute"):
                results = self.executor.execute(
                    index_name, query, slices or None, exec_opt)
            if lane == LANE_WRITE:
                # Commit barrier before the ack: every mutation this
                # query applied has its WAL record durable (per the
                # fsync policy) when the response goes out. Concurrent
                # write queries coalesce into one leader flush per
                # touched WAL (storage.wal group commit). Bound as the
                # thread's current query so a wal.append failpoint hit
                # during THIS query's barrier flags its context for
                # the tail sampler (the barrier covers its records).
                # The span says how many resident slabs the writes
                # retired, where they retired any.
                tags = {}
                if ctx.cost is not None and ctx.cost.retired_slabs:
                    tags["retired_slabs"] = ctx.cost.retired_slabs
                with ctx.stage("commit", **tags), sched_context.use(ctx):
                    storage_wal.barrier_all()
        except HTTPError as e:  # 429 from _admit / 507 write-unready
            err = e
            raise
        except QueryDeadlineError as e:
            err = e
            return error_resp(504, str(e),
                              headers=_resp_headers())
        except QueryKilledError as e:
            # Cost-policy kill (sched.tenants): a DISTINCT status so
            # clients tell a budget kill from an operator cancel, with
            # the policy named in the header contract.
            err = e
            hs = _resp_headers()
            hs.append((KILLED_BY_HEADER, KILL_POLICY))
            return error_resp(402, str(e), headers=hs)
        except QueryCancelledError as e:
            err = e
            return error_resp(409, str(e),
                              headers=_resp_headers())
        except storage_wal.WalError as e:
            # A commit barrier that failed on a FULL disk answers 507
            # + Retry-After (fault.diskfull already flipped the node
            # write-unready at the WAL site) — a retryable condition,
            # not a 500 crash-loop. Any other WAL failure stays a 500.
            err = e
            if not fault_diskfull.write_ready(probe=False):
                hs = _resp_headers()
                hs.append(("Retry-After", str(
                    fault_diskfull.default().retry_after_s())))
                return error_resp(
                    507, "insufficient storage: write not durable"
                         f" ({e})", headers=hs)
            self.logger.printf("query commit barrier failed: %s", e)
            return error_resp(500, str(e), headers=_resp_headers())
        except SliceUnavailableError as e:
            # No reachable (or trustworthy — storage quarantine) copy
            # of a touched slice anywhere: a 503 retryable condition,
            # not a 400 client error. ``?partial=1`` keeps the
            # degraded-answer contract instead (X-Pilosa-Partial).
            err = e
            return error_resp(503, f"slice unavailable: {e}",
                              headers=_resp_headers())
        except PilosaError as e:
            err = e
            return error_resp(400, str(e), headers=_resp_headers())
        except Exception as e:  # noqa: BLE001 - surfaced in response
            err = e
            self.logger.printf("query error: index=%s query=%.120s: %s",
                               index_name, query_str, e)
            return error_resp(500, str(e), headers=_resp_headers())
        finally:
            clock.switch("finish")
            if slot is not None:
                slot.release()
            if isinstance(err, HTTPError):
                status = err.status
            elif isinstance(err, QueryDeadlineError):
                status = 504
            elif isinstance(err, QueryKilledError):
                status = 402
            elif isinstance(err, QueryCancelledError):
                status = 409
            elif (isinstance(err, storage_wal.WalError)
                  and not fault_diskfull.write_ready(probe=False)):
                status = 507
            elif isinstance(err, SliceUnavailableError):
                status = 503
            elif isinstance(err, PilosaError):
                status = 400
            elif err is not None:
                status = 500
            else:
                status = 200
            # Tail-sampling keep decision (obs.sampler), BEFORE the
            # registry finishes the context so the slow-log entry can
            # cross-link the kept trace (traceKept / traceKeepReason).
            # Explicitly-requested traces ([trace] enabled, ?trace=1,
            # a coordinator-asked leg) keep unconditionally.
            if trace is not None:
                if ctx.cost is not None:
                    # Cost roll-up as span args: the perfetto view of
                    # this query carries its resource ledger.
                    trace.add_span("query_cost", ctx.started_wall, 0.0,
                                   tags=ctx.cost.summary())
                if ctx.plan is not None and (ctx.plan.sample
                                             or ctx.plan.analyze):
                    # Kept traces carry the plan fingerprint and the
                    # decision summary — a slow trace names the plan
                    # that produced it (/debug/plans has the tree).
                    # Sampled out on most shape hits (the ≤2% overhead
                    # budget); a shape's first sighting and every
                    # whole planning pass always carry it.
                    tags = {"fingerprint": ctx.plan.fingerprint}
                    tags.update(ctx.plan.decision_summary())
                    trace.add_span("query_plan", ctx.started_wall,
                                   0.0, tags=tags)
                reason = None
                if self.sampler is not None:
                    partial = bool(exec_opt is not None
                                   and exec_opt.partial
                                   and exec_opt.missing_slices)
                    reason = self.sampler.decide(
                        ctx, err=err, status=status, partial=partial)
                # Explicit keeps: [trace] enabled and ?trace=1 always;
                # a coordinator-asked remote leg only when no sampler
                # runs here — with tail sampling on, EVERY leg carries
                # the header (the spans piggyback back either way), so
                # auto-keeping would persist every healthy remote leg
                # on every peer. The peer's own tail decision keeps
                # the interesting legs.
                if reason is None and (
                        self.tracer.enabled
                        or req.query.get("trace") == "1"
                        or (trace_requested and remote
                            and self.sampler is None)):
                    reason = "requested"
                if trace.keep_reason:
                    # Already force-kept mid-flight (watchdog): it IS
                    # in the ring/disk — report that, don't re-enter,
                    # whatever the end-of-query decision said.
                    reason = trace.keep_reason
                elif reason is not None:
                    # keep() claims atomically: a watchdog force-keep
                    # racing this exact window wins and we report ITS
                    # reason instead of double-entering the ring/disk.
                    if self.tracer.keep(trace, reason=reason):
                        if self.sampler is not None:
                            self.sampler.persist(trace, reason,
                                                 ctx=ctx)
                    else:
                        reason = trace.keep_reason or reason
                ctx.trace_kept = reason is not None
                ctx.keep_reason = reason or ""
            if (ctx.plan is not None and not remote
                    and (ctx.plan.sample or ctx.plan.analyze)):
                # Per-fingerprint aggregation behind /debug/plans —
                # coordinator-only so a fleet of remote legs does not
                # multiply one query into N rows. A shape's first
                # sighting and a 1-in-16 slice of its hits record (an
                # unbiased duration reservoir); the rest skip the
                # bookkeeping.
                est = actual = None
                for root in ctx.plan.roots:
                    if (root.est_rows is not None
                            and root.actual_rows is not None):
                        est, actual = root.est_rows, root.actual_rows
                        break
                try:
                    self.executor.plan_store.record(
                        ctx.plan.fingerprint, ctx.plan.to_tree,
                        ctx.elapsed(), pql=query_str,
                        est_rows=est, actual_rows=actual)
                except Exception:  # noqa: BLE001 - observability only
                    pass
            # Canonical result digest (obs.capture): the value of
            # X-Pilosa-Result-Digest and the shadow-diff comparison
            # key. Coordinator-only (a remote leg's partial results
            # are not a client-visible answer) and success-only.
            if err is None and not remote:
                try:
                    ctx.result_digest = obs_capture.result_digest(
                        [codec.result_to_json(r) for r in results])
                except Exception:  # noqa: BLE001 - observability only
                    pass
            # Workload capture (obs.capture): append the replayable
            # record BEFORE registry.finish so the slow-log entry
            # cross-links the capture id. Disabled mode costs one
            # attribute read (the nop path the overhead guard proves).
            cap = self.capture
            if (cap is not None and cap.enabled and not remote
                    and cap.should_capture(ctx.lane)):
                opts = {}
                if req.query.get("timeout"):
                    opts["timeout"] = req.query["timeout"]
                if req.query.get("partial") == "1":
                    opts["partial"] = True
                ctx.capture_id = cap.add(
                    "query", query_str, index_name, ctx.tenant,
                    ctx.lane, ctx.id, status, ctx.elapsed(),
                    digest=ctx.result_digest,
                    plan=(ctx.plan.fingerprint
                          if ctx.plan is not None else ""),
                    opts=opts or None,
                    wall=ctx.started_wall, mono=ctx.started)
            self.registry.finish(ctx, error=err)
            # Latency histogram + outcome counter, labeled by call
            # type / lane / status (obs.metrics) — recorded for every
            # outcome, including 429/504/409 error returns.
            labels = (call_label, ctx.lane, str(status))
            # The latency observation carries the query id as an
            # OpenMetrics exemplar: "p99 regressed" comes with a trace
            # id to open (rendered only on OpenMetrics scrapes).
            obs_metrics.QUERY_SECONDS.labels(*labels).observe(
                ctx.elapsed(), exemplar={"trace_id": ctx.id})
            obs_metrics.QUERIES_TOTAL.labels(*labels).inc()
            # Per-tenant chargeback (sched.tenants): client-facing
            # latency/outcome on the COORDINATOR only (a remote leg
            # re-observing would double count the fleet roll-up);
            # cost units on EVERY node — each node's ledger holds its
            # own local work, so per-node increments sum correctly.
            tlabel = ctx.tenant or "default"
            if not remote:
                obs_metrics.TENANT_QUERY_SECONDS.labels(
                    tlabel).observe(ctx.elapsed())
                obs_metrics.TENANT_QUERIES.labels(
                    tlabel, str(status)).inc()
            cost = ctx.cost
            if cost is not None:
                rpc_b = sum(v["bytesOut"] + v["bytesIn"]
                            for v in cost.rpc.values())
                for resource, amount in (
                        ("container_ops",
                         sum(cost.container_ops.values())),
                        ("words_scanned", cost.words_scanned),
                        ("bits_written", cost.bits_written),
                        ("device_bytes", cost.device_bytes),
                        ("rpc_bytes", rpc_b),
                        ("queue_wait_ms",
                         int(ctx.stages.get("admission", 0.0) * 1e3)),
                        # Wall microseconds: the universal chargeback
                        # unit — kernel-fused paths can legitimately
                        # do zero container algebra, but every leg
                        # burns wall time on its node.
                        ("wall_us", int(ctx.elapsed() * 1e6))):
                    if amount:
                        obs_metrics.TENANT_COST_UNITS.labels(
                            tlabel, resource).inc(amount)

        # Optional column-attribute join (handler.go:208-227).
        attr_sets = []
        if column_attrs:
            idx = self.holder.index(index_name)
            arrs = [r.bits() for r in results if isinstance(r, Bitmap)]
            ids = (np.unique(np.concatenate(arrs)).tolist()
                   if arrs else [])
            for id in ids:
                attrs = idx.column_attr_store.attrs(id)
                if attrs:
                    attr_sets.append((id, attrs))

        # The id rides every response so clients can correlate with
        # /debug/queries (and DELETE a long-running follow-up); remote
        # legs piggyback spans (the encode span below is local-only).
        qid_hdr = _resp_headers()
        if exec_opt.partial and exec_opt.missing_slices:
            # The degraded-result contract: the client SEES which
            # slices are missing from these results.
            qid_hdr.append(("X-Pilosa-Partial", ",".join(
                str(s) for s in sorted(exec_opt.missing_slices))))
            obs_metrics.PARTIAL_RESULTS.inc()
        clock.switch("encode")
        if proto_out:
            return Response.proto(
                codec.encode_query_response(results, attr_sets),
                headers=qid_hdr)
        payload = codec.query_response_json(results, attr_sets)
        if req.query.get("profile") == "1" and ctx.cost is not None:
            # EXPLAIN ANALYZE for PQL: the merged per-node,
            # per-stage cost tree rides inline with the results
            # (remote legs' ledgers arrived as stitched children).
            payload["profile"] = ctx.cost.to_tree(dict(ctx.stages))
        if req.query.get("profile") == "1" and ctx.plan is not None:
            # The chosen plan with per-node est-vs-actual rows and
            # wall time, remote legs stitched in from
            # X-Pilosa-Plan headers.
            payload["plan"] = ctx.plan.to_tree()
        return Response.json(payload, headers=qid_hdr)

    # -- attr diff (anti-entropy) --------------------------------------------

    def _attr_diff(self, store, req: Request) -> Response:
        body = req.json()
        blocks = codec.blocks_from_json(body.get("blocks", []))
        attrs = {}
        for block_id in diff_blocks(store.blocks(), blocks):
            for id, m in store.block_data(block_id).items():
                attrs[str(id)] = m
        return Response.json({"attrs": attrs})

    def _handle_index_attr_diff(self, req: Request) -> Response:
        idx = self.holder.index(req.vars["index"])
        if idx is None:
            raise HTTPError(404, "index not found")
        return self._attr_diff(idx.column_attr_store, req)

    def _handle_frame_attr_diff(self, req: Request) -> Response:
        frame = self.holder.frame(req.vars["index"], req.vars["frame"])
        if frame is None:
            raise HTTPError(404, "frame not found")
        return self._attr_diff(frame.row_attr_store, req)

    # -- import / export -----------------------------------------------------

    def _handle_post_import(self, req: Request) -> Response:
        # Protobuf endpoint at reference parity (handler.go:896-906),
        # plus the raw-array sidecar format our own client negotiates
        # (proto/rawimport.py): protobuf varint-decodes every u64,
        # which was the measured wire-import bound; raw decodes as
        # np.frombuffer views.
        from ..proto import rawimport
        if req.content_type not in (_PROTOBUF, rawimport.CONTENT_TYPE):
            raise HTTPError(415, "Unsupported media type")
        # Strict 406 BEFORE body parsing, at reference parity for
        # protobuf callers; the raw sidecar also tolerates its own
        # type as Accept (pod-internal requests mirror Content-Type
        # into Accept) — the response is protobuf either way.
        if req.accept != _PROTOBUF and not (
                req.content_type == rawimport.CONTENT_TYPE
                and req.accept == rawimport.CONTENT_TYPE):
            raise HTTPError(406, "Not acceptable")
        # Per-stage instrumentation: the decode-vs-apply split is a
        # recorded histogram plus cost fields on the response.
        import time as time_mod
        decode_t0 = time_mod.perf_counter()
        wire_bytes = 0
        positions = None
        if req.content_type == rawimport.CONTENT_TYPE:
            body = req.body()
            wire_bytes = len(body)
            try:
                (index_name, frame_name, slice, rows, cols,
                 ts_ns, positions) = rawimport.decode(body)
            except ValueError as e:
                raise HTTPError(400, str(e))
        elif req.content_type == _PROTOBUF:
            body = req.body()
            wire_bytes = len(body)
            ireq = pb.ImportRequest.FromString(body)
            index_name, frame_name, slice = \
                ireq.Index, ireq.Frame, ireq.Slice
            n = len(ireq.RowIDs)
            rows = np.fromiter(ireq.RowIDs, np.uint64, n)
            cols = np.fromiter(ireq.ColumnIDs, np.uint64,
                               len(ireq.ColumnIDs))
            ts_ns = (np.fromiter(ireq.Timestamps, np.int64,
                                 len(ireq.Timestamps))
                     if ireq.Timestamps else None)
        decode_s = time_mod.perf_counter() - decode_t0
        obs_metrics.IMPORT_STAGE_SECONDS.labels("decode").observe(
            decode_s)
        if positions is not None:
            # Presorted positions form (rawimport v2): the sort is the
            # CLIENT's job, so sortedness is a contract, not a hint —
            # add_many would silently re-sort, but an unsorted body
            # means a broken client and the 400 keeps the wire
            # contract honest. One vectorized strictness pass.
            if len(positions) > 1 and not bool(
                    np.all(positions[:-1] < positions[1:])):
                raise HTTPError(
                    400, "raw-import positions not sorted-unique")
            n_bits = len(positions)
        elif len(rows) != len(cols) or (
                ts_ns is not None and len(ts_ns) != len(rows)):
            raise HTTPError(400, "import array length mismatch")
        else:
            n_bits = len(rows)
        if self.cluster is not None and not self.cluster.owns_fragment(
                self.host, index_name, slice):
            raise HTTPError(412, f"host does not own slice"
                                 f" {self.host}-{index_name}"
                                 f" slice:{slice}")
        idx = self.holder.index(index_name)
        if idx is None:
            raise HTTPError(404, "index not found")
        frame = idx.frame(frame_name)
        if frame is None:
            raise HTTPError(404, "frame not found")
        import datetime as dt
        if ts_ns is not None and ts_ns.any():
            timestamps = [
                dt.datetime.fromtimestamp(ts / 1e9, dt.timezone.utc)
                .replace(tzinfo=None) if ts else None
                for ts in ts_ns.tolist()]
        else:
            # A non-empty ALL-ZERO Timestamps list collapses to
            # timestamps=None here, where the reference (handler.go)
            # builds a per-bit slice of nils. End state is identical —
            # frame.import_bits treats a per-bit None exactly like no
            # timestamp — but any future PER-BIT timestamp semantics
            # must re-check this edge (ADVICE r5 #4).
            timestamps = None
        pod_view = req.query.get("podView")
        if pod_view is not None and pod_view not in ("standard", "inverse"):
            raise HTTPError(400, f"invalid podView: {pod_view}")
        if positions is not None and (
                frame.inverse_enabled or pod_view == "inverse"
                or (self.pod is not None and self.pod.is_coordinator
                    and pod_view is None)):
            # The positions form is the standard-view fast lane; a
            # frame that also needs the inverse transpose (or a pod
            # split by row slice) wants (row, col) pairs —
            # reconstruct them (three vector ops) and take the
            # generic path below.
            from .. import SLICE_WIDTH
            W = np.uint64(SLICE_WIDTH)
            rows = positions // W
            cols = np.uint64(slice) * W + (positions % W)
            positions = None
        apply_t0 = time_mod.perf_counter()
        # Pipeline depth: concurrent /import handlers in their apply
        # stage. >1 means a later block's decode (another connection
        # thread) overlapped this apply — the pipelined wire-import
        # path observable as a gauge.
        obs_metrics.IMPORT_PIPELINE_DEPTH.inc()
        try:
            with _APPLY_GATE:
                if positions is not None:
                    # Writable copy: frombuffer views of the request
                    # body are read-only, and container merges may
                    # keep slices of the batch vector alive — aliasing
                    # those to the HTTP body would pin whole request
                    # buffers in the holder.
                    frame.import_slice_positions(slice,
                                                 np.array(positions))
                elif (self.pod is not None and self.pod.is_coordinator
                        and pod_view is None):
                    self._pod_import(index_name, frame_name, slice,
                                     rows, cols, ts_ns, idx, frame,
                                     timestamps)
                else:
                    frame.import_bits(rows, cols, timestamps,
                                      views=pod_view)
        finally:
            obs_metrics.IMPORT_PIPELINE_DEPTH.inc(-1)
        apply_s = time_mod.perf_counter() - apply_t0
        obs_metrics.IMPORT_STAGE_SECONDS.labels("apply").observe(
            apply_s)
        # Commit barrier before the 200: fragment import lanes barrier
        # their own WAL, but a time-view fan-out (or a pod split) may
        # leave sibling fragments' records pending — the ack covers
        # them all, coalesced with concurrent imports' barriers.
        storage_wal.barrier_all()
        obs_metrics.IMPORT_BITS.labels("bits").inc(n_bits)
        # Workload capture (obs.capture): the import ack is a state
        # mutation replay must reproduce, so writes record in every
        # non-off mode (should_capture never samples the write lane).
        cap = self.capture
        if cap is not None and cap.enabled \
                and cap.should_capture(LANE_WRITE):
            tenant = (self.environ_header(req, "HTTP_X_PILOSA_TENANT")
                      or index_name)
            cap.add("import", "", index_name, tenant, LANE_WRITE, "",
                    200, decode_s + apply_s,
                    bits=n_bits, slice=int(slice),
                    frame=frame_name)
        # Cost fields ride the response: decode vs apply wall time and
        # the wire/bit volumes (the snapshot leg, when one triggers,
        # lands in the same histogram from the fragment).
        stats = json.dumps(
            {"decodeMs": round(decode_s * 1e3, 3),
             "applyMs": round(apply_s * 1e3, 3),
             "wireBytes": wire_bytes, "bits": n_bits},
            separators=(",", ":"))
        hs = [(obs_accounting.STATS_HEADER, stats)]
        # The import ack carries the written slice's fresh generation
        # tokens: an importing coordinator's map invalidates its
        # cached results for this slice on the ack itself, no extra
        # round trip (cluster.generations wire contract).
        gh = self._generations_header(index_name, [slice])
        if gh is not None:
            hs.append(gh)
        return Response.proto(pb.ImportResponse(), headers=hs)

    def _pod_import(self, index_name, frame_name, slice, rows, cols,
                    ts_ns, idx, frame, timestamps) -> None:
        """Split an import within the pod (parallel.pod placement):
        standard + time views live on the owner of the column slice;
        inverse views group by row slice, one leg per owning process.
        Owner resolution runs per UNIQUE row slice (a few jump-hash
        calls) and the bits group by owner in one vectorized pass —
        this was the last per-bit Python loop on an import path."""
        from .. import SLICE_WIDTH
        from ..utils.arrays import group_by_key
        pod = self.pod
        n = len(rows)
        if ts_ns is None:
            ts_ns = np.zeros(n, dtype=np.int64)

        owner = pod.owner_pid(slice)
        if owner == pod.pid:
            frame.import_bits(rows, cols, timestamps, views="standard")
        else:
            self._pod_forward_import(owner, index_name, frame_name,
                                     slice, rows, cols, ts_ns,
                                     "standard")
            idx.set_remote_max_slice(slice)

        if not frame.inverse_enabled or not n:
            return
        rslice = rows // np.uint64(SLICE_WIDTH)
        uniq_slices = np.unique(rslice)
        pid_arr = np.fromiter(
            (pod.owner_pid(int(s)) for s in uniq_slices.tolist()),
            np.int64, len(uniq_slices))
        pids = pid_arr[np.searchsorted(uniq_slices, rslice)]
        for pid, rs, cs, ii, sl in group_by_key(
                pids, rows, cols, np.arange(n), rslice):
            if pid == pod.pid:
                sub_ts = ([timestamps[i] for i in ii.tolist()]
                          if timestamps else None)
                frame.import_bits(rs, cs, sub_ts, views="inverse")
            else:
                self._pod_forward_import(
                    pid, index_name, frame_name, slice, rs, cs,
                    ts_ns[ii], "inverse")
                idx.set_remote_max_inverse_slice(int(sl.max()))

    def _pod_forward_import(self, pid: int, index: str, frame: str,
                            slice: int, rows, cols, ts_ns,
                            view: str) -> None:
        # Pod-internal legs are always us-to-us: raw arrays, no
        # negotiation needed.
        from ..proto import rawimport
        ts = np.asarray(ts_ns)
        body = rawimport.encode(index, frame, slice,
                                np.asarray(rows, dtype=np.uint64),
                                np.asarray(cols, dtype=np.uint64),
                                ts if ts.any() else None)
        self.pod.forward_raw(pid, "POST", f"/import?podView={view}",
                             body, rawimport.CONTENT_TYPE)

    def _handle_get_export(self, req: Request) -> Response:
        if req.accept != "text/csv":
            raise HTTPError(406, "Not acceptable")
        slice = req.uint_param("slice")
        index = req.query.get("index", "")
        if self.cluster is not None and not self.cluster.owns_fragment(
                self.host, index, slice):
            raise HTTPError(412, f"host does not own slice {self.host}"
                                 f"-{index} slice:{slice}")
        frag = self.holder.fragment(index, req.query.get("frame", ""),
                                    req.query.get("view", ""), slice)
        if frag is None:
            return Response(200, b"", "text/csv")
        return Response(200, _export_csv_chunks(frag), "text/csv")

    # -- fragment endpoints --------------------------------------------------

    def _fragment_from_query(self, req: Request):
        slice = req.uint_param("slice")
        return self.holder.fragment(req.query.get("index", ""),
                                    req.query.get("frame", ""),
                                    req.query.get("view", ""), slice)

    def _handle_fragment_nodes(self, req: Request) -> Response:
        slice = req.uint_param("slice")
        index = req.query.get("index", "")
        nodes = (self.cluster.fragment_nodes(index, slice)
                 if self.cluster else [])
        return Response.json([{"host": n.host,
                               "internalHost": n.internal_host}
                              for n in nodes])

    @staticmethod
    def _refuse_quarantined(frag) -> None:
        """Storage integrity: a quarantined fragment's copy (corrupt,
        or the fresh near-empty replacement awaiting repair) must not
        feed a peer's anti-entropy vote, a resize diff, or a backup —
        409 so remote consumers skip this node and sweep again after
        repair. The local repairer bypasses HTTP (server.repair's
        in-process target adapter)."""
        if frag is not None and getattr(frag, "quarantined", False):
            raise HTTPError(409, "fragment quarantined: "
                                 + frag.quarantine_reason)

    def _handle_fragment_blocks(self, req: Request) -> Response:
        frag = self._fragment_from_query(req)
        if frag is None:
            raise HTTPError(404, "fragment not found")
        self._refuse_quarantined(frag)
        return Response.json({"blocks": codec.blocks_to_json(frag.blocks())})

    def _handle_fragment_block_data(self, req: Request) -> Response:
        breq = pb.BlockDataRequest.FromString(req.body())
        frag = self.holder.fragment(breq.Index, breq.Frame, breq.View,
                                    breq.Slice)
        if frag is None:
            raise HTTPError(404, "fragment not found")
        self._refuse_quarantined(frag)
        ps = frag.block_data(breq.Block)
        return Response.proto(pb.BlockDataResponse(
            RowIDs=[int(r) for r in ps.row_ids],
            ColumnIDs=[int(c) for c in ps.column_ids]))

    def _handle_get_fragment_data(self, req: Request) -> Response:
        frag = self._fragment_from_query(req)
        if frag is None:
            raise HTTPError(404, "fragment not found")
        self._refuse_quarantined(frag)
        if req.query.get("snapshot") == "1":
            # The backup coordinator's per-fragment barrier: fold the
            # WAL into a fresh footered snapshot so the streamed body
            # verifies standalone and carries no op tail. A CLEAN
            # fragment (empty op tail, footered file — the tier
            # demote path's condition) skips the rewrite+fsync: the
            # on-disk file already IS that snapshot, and repeated
            # backup passes must not pay (or contend on) a full
            # rewrite per fragment.
            try:
                clean = False
                try:
                    frag.wal_barrier()
                    clean = (frag.storage.op_n == 0
                             and getattr(frag.storage, "footer",
                                         None) is not None)
                except storage_wal.WalError:
                    clean = False  # torn pending tail: fold it
                if not clean:
                    frag.snapshot(sync=True, reason="backup")
            except OSError as e:
                raise HTTPError(500, f"snapshot failed: {e}")
        # Spool to disk above 8 MB so concurrent 128 MB+ backups don't
        # each hold the whole archive in memory.
        import tempfile
        spool = tempfile.SpooledTemporaryFile(max_size=8 << 20)
        frag.write_to(spool)
        spool.seek(0)
        return Response(200, spool, "application/octet-stream")

    # -- generation tokens (cluster.generations) -----------------------------

    def _owned_slices(self, index_name: str) -> list[int]:
        """The slices this node would report tokens for when a caller
        names none: every locally-owned slice of the index."""
        idx = self.holder.index(index_name)
        if idx is None:
            return []
        max_slice = idx.max_slice()
        if self.cluster is None:
            return list(range(max_slice + 1))
        return [int(s) for s in self.cluster.owns_slices(
            index_name, max_slice, self.host)]

    def _generations_header(self, index_name: str,
                            slices) -> Optional[tuple]:
        """(header, payload) with this node's current tokens for the
        served slices, or None when there is nothing to report. Never
        raises — a token header must not fail the response that
        carries it."""
        try:
            if self.holder is None or self.holder.index(index_name) \
                    is None:
                return None
            if not slices:
                slices = self._owned_slices(index_name)
            if not slices:
                return None
            tokens = gens_mod.local_tokens(self.holder, index_name,
                                           slices)
            return (gens_mod.GENERATIONS_HEADER,
                    gens_mod.encode_wire(index_name, tokens))
        except Exception:  # noqa: BLE001 - advisory header only
            return None

    def _handle_get_generations(self, req: Request) -> Response:
        """The coordinator result cache's validation probe: current
        per-fragment (uid, generation) tokens for the named slices
        (default: every locally-owned slice). A cheap read — no locks
        beyond the holder maps — so a validation round-trip costs
        ~RTT, not a query."""
        index_name = req.query.get("index", "")
        if not index_name:
            raise HTTPError(400, "index required")
        if self.holder.index(index_name) is None:
            raise HTTPError(404, "index not found")
        raw = req.query.get("slices", "")
        try:
            slices = [int(s) for s in raw.split(",") if s != ""]
        except ValueError:
            raise HTTPError(400, "invalid slices argument")
        if not slices:
            slices = self._owned_slices(index_name)
        tokens = gens_mod.local_tokens(self.holder, index_name, slices)
        return Response.json({
            "index": index_name, "host": self.host,
            "tokens": {str(s): {k: [v[0], v[1]] for k, v in m.items()}
                       for s, m in tokens.items()}})

    # -- elastic resize (cluster.resize; docs/CLUSTER_RESIZE.md) -------------

    def _resize_server(self):
        """The Server behind the resize control surface; bare test
        handlers (no status_handler / no start_resize) answer 503."""
        s = self.status_handler
        if s is None or not hasattr(s, "start_resize"):
            raise HTTPError(503, "no resize coordinator on this node")
        return s

    def _handle_get_cluster_resize(self, req: Request) -> Response:
        out: dict = {"epoch": self.cluster.epoch
                     if self.cluster is not None else 0}
        rs = self.cluster.resize if self.cluster is not None else None
        out["installed"] = rs.to_wire() if rs is not None else None
        s = self.status_handler
        op = getattr(s, "resize_op", None)
        out["op"] = op.status() if op is not None else None
        return Response.json(out)

    def _handle_post_cluster_resize(self, req: Request) -> Response:
        """Start (or abort) an online resize with THIS node as
        coordinator. Body: {"hosts": [target membership]} |
        {"add": "h:p"} | {"remove": "h:p"} | {"abort": true}."""
        server = self._resize_server()
        body = req.json()
        if body.get("abort"):
            status = server.abort_resize()
            if status is None:
                raise HTTPError(409, "no resize in flight")
            return Response.json({"op": status})
        current = [n.host for n in self.cluster.nodes]
        if body.get("hosts"):
            target = [str(h) for h in body["hosts"]]
        elif body.get("add"):
            h = str(body["add"])
            if h in current:
                raise HTTPError(400, f"{h} already a member")
            target = current + [h]
        elif body.get("remove"):
            h = str(body["remove"])
            if h not in current:
                raise HTTPError(400, f"{h} not a member")
            if len(current) == 1:
                raise HTTPError(400, "cannot remove the last node")
            target = [x for x in current if x != h]
        else:
            raise HTTPError(400, "hosts, add, remove, or abort"
                                 " required")
        try:
            coord = server.start_resize(target)
        except PilosaError as e:
            raise HTTPError(409, str(e))
        return Response.json({"op": coord.status()}, status=202)

    # -- backup control surface (backup.coordinator) --------------------------

    def _backup_server(self):
        """The Server behind the backup control surface; bare test
        handlers (no status_handler / no start_backup) answer 503."""
        s = self.status_handler
        if s is None or not hasattr(s, "start_backup"):
            raise HTTPError(503, "no backup coordinator on this node")
        return s

    def _handle_get_backup(self, req: Request) -> Response:
        """The in-flight (or last finished) backup this node
        coordinates, plus whether an archive is configured."""
        s = self.status_handler
        op = getattr(s, "backup_op", None)
        return Response.json({
            "configured": getattr(s, "backup_store", None) is not None,
            "op": op.status() if op is not None else None})

    def _handle_post_backup(self, req: Request) -> Response:
        """Start (or abort) a cluster backup with THIS node as
        coordinator. Body: {"kind": "full"|"incremental"} |
        {"abort": true}."""
        server = self._backup_server()
        body = req.json()
        if body.get("abort"):
            status = server.abort_backup()
            if status is None:
                raise HTTPError(409, "no backup in flight")
            return Response.json({"op": status})
        kind = str(body.get("kind", "full"))
        if kind not in ("full", "incremental"):
            raise HTTPError(400, f"unknown backup kind {kind!r}")
        try:
            coord = server.start_backup(kind)
        except PilosaError as e:
            raise HTTPError(409, str(e))
        return Response.json({"op": coord.status()}, status=202)

    def _handle_debug_backup(self, req: Request) -> Response:
        """Archive introspection: committed backups (lineage, sizes),
        WAL-archive coverage per node, this node's archiver state, and
        the in-flight op — the first stop of any is-my-data-safe
        check."""
        s = self.status_handler
        store = getattr(s, "backup_store", None)
        out: dict = {"configured": store is not None}
        op = getattr(s, "backup_op", None)
        out["op"] = op.status() if op is not None else None
        archiver = getattr(s, "wal_archiver", None)
        out["walArchiver"] = (archiver.state()
                              if archiver is not None else None)
        if store is not None:
            from ..backup import archive as backup_archive
            out["backups"] = [
                {"id": m["id"], "kind": m.get("kind"),
                 "parent": m.get("parent"), "t": m.get("t"),
                 "coordinator": m.get("coordinator"),
                 "epoch": m.get("epoch"),
                 "fragments": len(m.get("fragments", []))}
                for m in backup_archive.list_backups(store)]
            wal: dict = {}
            for _key, node, seq in backup_archive.list_wal_segments(
                    store):
                ent = wal.setdefault(node,
                                     {"segments": 0, "maxSeq": -1})
                ent["segments"] += 1
                ent["maxSeq"] = max(ent["maxSeq"], seq)
            out["walSegments"] = wal
        return Response.json(out)

    def _handle_debug_topology(self, req: Request) -> Response:
        """Placement introspection: the epoch, the membership, every
        index's per-slice owner map, and the in-flight resize state —
        the first thing a mis-routed-query investigation needs."""
        if self.cluster is None:
            return Response.json({"epoch": 0, "nodes": [],
                                  "indexes": {}, "resize": None})
        cl = self.cluster
        rs = cl.resize
        out: dict = {
            "epoch": cl.epoch,
            "partitionN": cl.partition_n,
            "replicaN": cl.replica_n,
            "nodes": [n.host for n in cl.nodes],
            "resize": rs.to_wire() if rs is not None else None,
        }
        indexes: dict = {}
        if self.holder is not None:
            for name in sorted(self.holder.indexes):
                idx = self.holder.indexes[name]
                hi = max(idx.max_slice(), idx.max_inverse_slice())
                owners = {}
                moving = []
                for s in range(hi + 1):
                    owners[str(s)] = [n.host for n in
                                      cl.fragment_nodes(name, s)]
                    if cl.moving_slice(name, s) is not None:
                        moving.append(s)
                entry: dict = {"maxSlice": idx.max_slice(),
                               "owners": owners}
                if moving:
                    entry["movingSlices"] = moving
                indexes[name] = entry
        out["indexes"] = indexes
        return Response.json(out)

    def _handle_post_fragment_import(self, req: Request) -> Response:
        """Additive per-fragment positions import — the resize
        streamer's push lane (cluster.client.fragment_import). Unlike
        POST /fragment/data it never replaces content (concurrent
        double-writes land between a block-diff read and this push);
        unlike /import it applies to the EXACT (frame, view) fragment
        so inverse and time views migrate faithfully. Body: LE u64
        slice-local positions (row*SLICE_WIDTH + col%SLICE_WIDTH)."""
        index_name = req.query.get("index", "")
        frame_name = req.query.get("frame", "")
        view = req.query.get("view", "")
        slice = req.uint_param("slice")
        if not index_name or not frame_name or not view:
            raise HTTPError(400, "index, frame, and view required")
        if self.cluster is not None and not self.cluster.owns_fragment(
                self.host, index_name, slice):
            raise HTTPError(412, f"host does not own slice"
                                 f" {self.host}-{index_name}"
                                 f" slice:{slice}")
        frame = self.holder.frame(index_name, frame_name)
        if frame is None:
            raise HTTPError(404, "frame not found")
        body = req.body()
        if len(body) % 8:
            raise HTTPError(400, "positions body not 8-byte aligned")
        positions = np.frombuffer(body, dtype="<u8")
        v = frame.create_view_if_not_exists(view)
        frag = v.create_fragment_if_not_exists(slice)
        if len(positions):
            # Writable sorted copy: frombuffer views of the HTTP body
            # are read-only and may alias the request buffer.
            frag.import_positions(np.sort(positions))
        storage_wal.barrier_all()
        obs_metrics.IMPORT_BITS.labels("resizeStream").inc(
            len(positions))
        hs = []
        gh = self._generations_header(index_name, [slice])
        if gh is not None:
            hs.append(gh)
        return Response.json({"accepted": int(len(positions))},
                             headers=hs)

    def _handle_post_fragment_data(self, req: Request) -> Response:
        slice = req.uint_param("slice")
        frame = self.holder.frame(req.query.get("index", ""),
                                  req.query.get("frame", ""))
        if frame is None:
            raise HTTPError(404, "frame not found")
        view = frame.create_view_if_not_exists(req.query.get("view", ""))
        frag = view.create_fragment_if_not_exists(slice)
        # Spool the body to a bounded temp file BEFORE read_from: the
        # restore swaps storage under the fragment lock, which must be
        # held at disk speed, not for a slow client's whole upload —
        # and an aborted upload then never reaches the storage swap.
        import shutil
        import tempfile
        with tempfile.SpooledTemporaryFile(max_size=1 << 24) as spool:
            shutil.copyfileobj(req.body_stream(), spool, 1 << 20)
            spool.seek(0)
            frag.read_from(spool)
        return Response.json({})

    def _handle_post_frame_restore(self, req: Request) -> Response:
        # Pull every owned slice of a frame from a remote cluster
        # (handler.go:1180-1266).
        index_name, frame_name = req.vars["index"], req.vars["frame"]
        host = req.query.get("host")
        if not host:
            raise HTTPError(400, "host required")
        if self.client_factory is None:
            raise HTTPError(500, "no client factory configured")
        client = self.client_factory(host)
        max_slices = client.max_slices()
        frame = self.holder.frame(index_name, frame_name)
        if frame is None:
            raise HTTPError(404, "frame not found")
        views = client.frame_views(index_name, frame_name)
        for slice in range(max_slices.get(index_name, 0) + 1):
            if self.cluster is not None and not self.cluster.owns_fragment(
                    self.host, index_name, slice):
                continue
            for view_name in views:
                view = frame.create_view_if_not_exists(view_name)
                frag = view.create_fragment_if_not_exists(slice)
                rd = client.backup_slice(index_name, frame_name, view_name,
                                         slice)
                if rd is None:
                    continue
                try:
                    frag.read_from(rd)
                finally:
                    rd.close()
        return Response.json({})

    # -- pod work items (parallel.pod) ---------------------------------------

    def _handle_pod_exec(self, req: Request) -> Response:
        if self.pod is None:
            raise HTTPError(404, "not a pod process")
        return Response.json(self.pod.run_item(req.json()))

    # -- broadcast ingest ----------------------------------------------------

    def _handle_post_message(self, req: Request) -> Response:
        if self.broadcast_handler is None:
            raise HTTPError(404, "no broadcast handler")
        self.broadcast_handler.receive_message(
            unmarshal_message(req.body()))
        return Response.json({})
