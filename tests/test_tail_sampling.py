"""Tail-sampled tracing: the keep-reason decision, the crash-safe
on-disk segment ring, and the handler integration (every query buffers
spans; the interesting ones persist and the slow log cross-links
them). docs/OBSERVABILITY.md is the operator-facing contract."""

import io
import json
import os
import time

import pytest

from pilosa_tpu.errors import QueryCancelledError, QueryDeadlineError
from pilosa_tpu.executor import Executor
from pilosa_tpu.fault import failpoints
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.obs import metrics as obs_metrics
from pilosa_tpu.obs.diskring import SegmentRing
from pilosa_tpu.obs.sampler import (TailSampler, record_to_trace,
                                    trace_record)
from pilosa_tpu.obs.trace import Trace, Tracer
from pilosa_tpu.sched import AdmissionController, QueryContext
from pilosa_tpu.server.handler import Handler


def call(app, method, path, body=b"", content_type="", accept="",
         headers=None):
    if "?" in path:
        path, _, qs = path.partition("?")
    else:
        qs = ""
    environ = {"REQUEST_METHOD": method, "PATH_INFO": path,
               "QUERY_STRING": qs, "CONTENT_LENGTH": str(len(body)),
               "wsgi.input": io.BytesIO(body)}
    if content_type:
        environ["CONTENT_TYPE"] = content_type
    if accept:
        environ["HTTP_ACCEPT"] = accept
    for k, v in (headers or {}).items():
        environ["HTTP_" + k.upper().replace("-", "_")] = v
    out = {}

    def start_response(status, hs):
        out["status"] = int(status.split()[0])
        out["headers"] = dict(hs)

    chunks = app(environ, start_response)
    return out["status"], out["headers"], b"".join(chunks)


# -- the disk segment ring -----------------------------------------------------


class TestSegmentRing:
    def test_round_trip_and_rotation(self, tmp_path):
        ring = SegmentRing(str(tmp_path / "r"), segment_bytes=4096,
                           max_segments=3)
        for i in range(200):
            assert ring.append({"i": i, "pad": "x" * 64})
        got = [r["i"] for r in ring.scan()]
        # Newest first, oldest rotated away, disk bounded.
        assert got[0] == 199
        assert got == sorted(got, reverse=True)
        assert len(got) < 200
        stats = ring.stats()
        assert stats["segments"] <= 3
        assert stats["bytes"] <= 3 * 4096 + 4096
        assert stats["written"] == 200
        ring.close()

    def test_reopen_serves_persisted_records(self, tmp_path):
        d = str(tmp_path / "r")
        ring = SegmentRing(d)
        for i in range(5):
            ring.append({"i": i})
        ring.close()
        reopened = SegmentRing(d)
        assert [r["i"] for r in reopened.scan()] == [4, 3, 2, 1, 0]
        # New appends land in a FRESH segment past the old ones.
        reopened.append({"i": 5})
        assert [r["i"] for r in reopened.scan()][0] == 5
        reopened.close()

    def test_torn_write_skips_bad_segment_serves_rest(self, tmp_path):
        """The crash-safety contract: a torn segment write (the
        ring.write failpoint tears mid-record, as SIGKILL would) ends
        that segment's scan at the tear; whole records before it and
        every other segment still serve after reopen."""
        d = str(tmp_path / "r")
        ring = SegmentRing(d, segment_bytes=1 << 16)
        ring.append({"i": 0})
        ring.append({"i": 1})
        with failpoints.injected("ring.write", "torn(7)*1"):
            assert ring.append({"i": 2}) is False
        assert ring.dropped == 1
        # Post-tear appends open a fresh segment and serve.
        ring.append({"i": 3})
        got = [r["i"] for r in ring.scan()]
        assert got == [3, 1, 0], got  # 2 is gone, nothing else is
        assert ring.skipped >= 1
        ring.close()
        # Reopen (the restart path): same records, same skip.
        reopened = SegmentRing(d)
        assert [r["i"] for r in reopened.scan()] == [3, 1, 0]
        reopened.close()

    def test_sigkill_mid_write_torn_tail_trimmed(self, tmp_path):
        """A raw torn tail on disk (process killed mid-write(2), no
        exception ever raised in-process): reopen serves every whole
        record and stops at the tear."""
        d = str(tmp_path / "r")
        ring = SegmentRing(d)
        ring.append({"i": 0})
        ring.append({"i": 1})
        ring.close()
        segs = sorted(os.listdir(d))
        path = os.path.join(d, segs[-1])
        with open(path, "ab") as f:  # half a record, as SIGKILL leaves
            f.write(b"deadbeef {\"i\": 2, \"trunca")
        reopened = SegmentRing(d)
        assert [r["i"] for r in reopened.scan()] == [1, 0]
        assert reopened.skipped == 1
        # Corrupt a MIDDLE byte of the first record of a fresh
        # segment: crc catches silent corruption, not just length.
        reopened.append({"i": 3})
        reopened.close()
        segs2 = sorted(os.listdir(d))
        assert len(segs2) == 2
        with open(os.path.join(d, segs2[-1]), "r+b") as f:
            f.seek(12)
            f.write(b"X")
        again = SegmentRing(d)
        assert [r["i"] for r in again.scan()] == [1, 0]
        again.close()


# -- the keep decision ---------------------------------------------------------


class TestKeepDecision:
    def _sampler(self, **kw):
        kw.setdefault("head_n", 0)
        kw.setdefault("histogram", obs_metrics.Histogram(
            "pilosa_test_decide_latency_seconds", buckets=(0.1, 1.0)))
        return TailSampler(**kw)

    def test_outcome_reasons(self):
        s = self._sampler()
        ctx = QueryContext(pql="q")
        assert s.decide(ctx, err=QueryDeadlineError("x")) == "deadline"
        assert s.decide(ctx, err=QueryCancelledError("x")) == "cancelled"
        assert s.decide(ctx, err=RuntimeError("x")) == "error"
        assert s.decide(ctx, status=504) == "deadline"
        assert s.decide(ctx, status=429) == "shed"
        assert s.decide(ctx, status=500) == "error"
        assert s.decide(ctx, partial=True) == "partial"
        assert s.decide(ctx) is None

    def test_fault_flags(self):
        s = self._sampler()
        for flag, reason in (("breaker", "breaker"),
                             ("failover", "breaker"),
                             ("failpoint", "failpoint"),
                             ("partial", "partial")):
            ctx = QueryContext(pql="q")
            ctx.note_flag(flag)
            assert s.decide(ctx) == reason, flag

    def test_shed_lane_window(self):
        adm = AdmissionController(concurrency=1, queue_depth=0)
        s = self._sampler(admission=adm)
        ctx = QueryContext(pql="q", lane="read")
        assert s.decide(ctx) is None
        slot = adm.acquire("read")
        with pytest.raises(Exception):
            adm.acquire("read")  # queue_depth=0 -> immediate 429
        assert s.decide(ctx) == "shed"
        slot.release()

    def test_dynamic_slow_threshold_tracks_histogram(self):
        hist = obs_metrics.Histogram(
            "pilosa_test_slowthresh_latency_seconds",
            buckets=(0.01, 0.1, 1.0))
        s = self._sampler(histogram=hist, slow_floor_s=0.001)
        # Cold: too few observations -> conservative fixed threshold.
        assert s.slow_threshold_s() == 0.5
        for _ in range(200):
            hist.observe(0.005)
        s._threshold = (0.0, 0.0)  # expire the cache
        # p99 of an all-fast workload: the first bucket bound.
        assert s.slow_threshold_s() == 0.01
        ctx = QueryContext(pql="q", timeout_s=None)
        ctx.started -= 0.05  # elapsed ~50ms > 10ms threshold
        assert s.decide(ctx) == "slow"

    def test_head_sample_one_in_n(self):
        s = TailSampler(head_n=10, histogram=obs_metrics.Histogram(
            "pilosa_test_head_latency_seconds", buckets=(0.1,)))
        ctx = QueryContext(pql="q")
        kept = [s.decide(ctx) for _ in range(30)]
        assert kept.count("head") == 3
        assert kept[0] == "head"  # the first query of a process keeps

    def test_persist_round_trip(self, tmp_path):
        ring = SegmentRing(str(tmp_path / "t"))
        s = self._sampler(disk=ring)
        trace = Trace("qid1", node="n1", pql="Count(...)")
        trace.add_span("execute", time.time(), 0.0)
        ctx = QueryContext(pql="Count(...)", index="i")
        s.persist(trace, "slow", ctx=ctx)
        rec = next(ring.scan())
        assert rec["id"] == "qid1" and rec["reason"] == "slow"
        assert rec["index"] == "i"
        rebuilt = record_to_trace(rec)
        assert rebuilt.keep_reason == "slow"
        assert [sp.name for sp in rebuilt.spans()] == ["execute"]
        chrome = rebuilt.to_chrome()
        assert chrome["otherData"]["traceId"] == "qid1"
        ring.close()


# -- handler integration -------------------------------------------------------


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "data"))
    h.open()
    yield h
    h.close()


@pytest.fixture
def tail_handler(holder, tmp_path):
    """A bare handler with tail sampling wired, over a real executor
    (the server wires the same objects in open())."""
    tracer = Tracer(enabled=False)
    sampler = TailSampler(
        disk=SegmentRing(str(tmp_path / "traces")),
        head_n=0, slow_floor_s=30.0,
        histogram=obs_metrics.Histogram(
            "pilosa_test_tailhandler_latency_seconds", buckets=(64.0,)))
    h = Handler(holder, Executor(holder, host="local"), host="local",
                tracer=tracer, sampler=sampler)
    return h


class TestHandlerTailSampling:
    def _seed(self, app):
        status, _, _ = call(app, "POST", "/index/ti", b"{}")
        assert status == 200
        status, _, _ = call(app, "POST", "/index/ti/frame/f", b"{}")
        assert status == 200
        status, _, body = call(
            app, "POST", "/index/ti/query",
            b'SetBit(frame="f", rowID=1, columnID=1)')
        assert status == 200, body

    def test_healthy_fast_query_not_kept(self, tail_handler):
        self._seed(tail_handler)
        status, headers, _ = call(tail_handler, "POST",
                                  "/index/ti/query",
                                  b'Count(Bitmap(frame="f", rowID=1))')
        assert status == 200
        qid = headers["X-Pilosa-Query-Id"]
        _, _, body = call(tail_handler, "GET", "/debug/traces")
        listing = json.loads(body)
        assert listing["tail"] is True
        assert not any(t["id"] == qid for t in listing["traces"])
        assert list(tail_handler.sampler.disk.scan()) == []

    def test_error_query_kept_with_reason_and_persisted(
            self, tail_handler):
        self._seed(tail_handler)
        status, headers, _ = call(
            tail_handler, "POST", "/index/ti/query",
            b'Plugin(frame="f")')  # parses, fails in the executor
        assert status == 400
        qid = headers["X-Pilosa-Query-Id"]
        _, _, body = call(tail_handler, "GET", "/debug/traces")
        entry = next(t for t in json.loads(body)["traces"]
                     if t["id"] == qid)
        assert entry["reason"] == "error"
        # Persisted: the disk listing filters by reason, and the
        # by-id route falls back to disk.
        _, _, body = call(tail_handler, "GET",
                          "/debug/traces?source=disk&reason=error")
        disk = json.loads(body)
        assert disk["source"] == "disk"
        assert any(t["id"] == qid for t in disk["traces"])
        _, _, body = call(tail_handler, "GET",
                          f"/debug/traces/{qid}?source=disk")
        assert json.loads(body)["otherData"]["traceId"] == qid

    def test_failpoint_hit_keeps_trace(self, tail_handler):
        """A query whose commit barrier hits an armed wal.append
        failpoint (delay mode — the injection fires, the write
        proceeds) is kept with reason "failpoint"."""
        self._seed(tail_handler)
        kept_ids = []
        with failpoints.injected("wal.append", "delay(1ms)"):
            for i in range(3):
                status, headers, _ = call(
                    tail_handler, "POST", "/index/ti/query",
                    f'SetBit(frame="f", rowID=2, columnID={i})'
                    .encode())
                assert status == 200
                kept_ids.append(headers["X-Pilosa-Query-Id"])
        _, _, body = call(tail_handler, "GET",
                          "/debug/traces?reason=failpoint")
        traces = json.loads(body)["traces"]
        assert any(t["id"] in kept_ids for t in traces), traces

    def test_slow_log_cross_links_kept_trace(self, holder, tmp_path):
        from pilosa_tpu.sched import QueryRegistry
        registry = QueryRegistry(slow_threshold_s=1e-9)
        sampler = TailSampler(
            disk=None, head_n=0, slow_floor_s=30.0,
            histogram=obs_metrics.Histogram(
                "pilosa_test_crosslink_latency_seconds",
                buckets=(64.0,)))
        h = Handler(holder, Executor(holder, host="local"),
                    host="local", registry=registry, sampler=sampler)
        call(h, "POST", "/index/tj", b"{}")
        call(h, "POST", "/index/tj/frame/f", b"{}")
        # An erroring query: kept (reason "error") + slow-logged.
        status, headers, _ = call(h, "POST", "/index/tj/query",
                                  b'Plugin(frame="f")')
        assert status == 400
        qid = headers["X-Pilosa-Query-Id"]
        _, _, body = call(h, "GET", "/debug/queries/slow")
        entry = next(e for e in json.loads(body)["slow"]
                     if e["id"] == qid)
        assert entry["traceKept"] is True
        assert entry["traceKeepReason"] == "error"
        # A healthy query's slow entry records the negative too.
        status, headers, _ = call(
            h, "POST", "/index/tj/query",
            b'SetBit(frame="f", rowID=1, columnID=1)')
        assert status == 200
        qid2 = headers["X-Pilosa-Query-Id"]
        _, _, body = call(h, "GET", "/debug/queries/slow")
        entry2 = next(e for e in json.loads(body)["slow"]
                      if e["id"] == qid2)
        assert entry2["traceKept"] is False
        assert "traceKeepReason" not in entry2

    def test_explicit_trace_still_kept_as_requested(self, tail_handler):
        self._seed(tail_handler)
        status, headers, _ = call(
            tail_handler, "POST", "/index/ti/query?trace=1",
            b'Count(Bitmap(frame="f", rowID=1))')
        assert status == 200
        qid = headers["X-Pilosa-Query-Id"]
        _, _, body = call(tail_handler, "GET", "/debug/traces")
        entry = next(t for t in json.loads(body)["traces"]
                     if t["id"] == qid)
        assert entry["reason"] == "requested"


class TestTraceRecordShape:
    def test_record_carries_cost_and_stages(self):
        from pilosa_tpu.obs import accounting
        ctx = QueryContext(pql="q", index="i")
        accounting.attach(ctx, node="n1")
        with ctx.stage("execute"):
            time.sleep(0.002)
        trace = Trace("qid2", node="n1", pql="q")
        rec = trace_record(trace, "deadline", ctx=ctx)
        assert rec["reason"] == "deadline"
        assert rec["stages"]["execute"] >= 0.002
        assert "cost" in rec
