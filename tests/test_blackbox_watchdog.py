"""Blackbox flight recorder + stall watchdog (docs/OBSERVABILITY.md):
periodic snapshots into a bounded disk ring, full dumps on demand, and
the four stall detectors — most importantly, a failpoint-wedged WAL
flusher must trip the watchdog and produce a dump that NAMES the
wedged WAL."""

import io
import json
import os
import threading
import time

import pytest

from pilosa_tpu.fault import failpoints
from pilosa_tpu.obs import metrics as obs_metrics
from pilosa_tpu.obs.blackbox import Blackbox
from pilosa_tpu.obs.diskring import SegmentRing
from pilosa_tpu.obs.sampler import TailSampler
from pilosa_tpu.obs.trace import Tracer
from pilosa_tpu.obs.watchdog import Watchdog
from pilosa_tpu.sched import (AdmissionController, QueryContext,
                              QueryRegistry)
from pilosa_tpu.storage import wal as storage_wal


# -- blackbox ------------------------------------------------------------------


class TestBlackbox:
    def test_snapshot_ring_and_dump(self, tmp_path):
        state = {"admission": {"queued": {}}, "note": "hello"}
        bb = Blackbox(str(tmp_path / "bb"), state_fn=lambda: state,
                      interval_s=60.0, node="n1")
        for _ in range(3):
            bb.snapshot("periodic")
        recent = list(bb.ring.scan())
        assert len(recent) == 3
        assert recent[0]["note"] == "hello"
        assert recent[0]["node"] == "n1"
        path = bb.dump("api")
        assert path is not None and os.path.exists(path)
        with open(path) as f:
            doc = json.load(f)
        assert doc["cause"] == "api"
        # The dump carries the whole ring (oldest first) plus a fresh
        # "current" snapshot taken at dump time.
        assert len(doc["ring"]) == 4  # 3 periodic + the dump's own
        assert doc["current"]["trigger"] == "dump:api"
        bb.stop()

    def test_dump_files_bounded(self, tmp_path):
        bb = Blackbox(str(tmp_path / "bb"), state_fn=dict,
                      interval_s=60.0, max_dumps=2)
        paths = [bb.dump(f"api") for _ in range(4)]
        assert all(paths)
        assert len(bb.dumps()) == 2  # oldest pruned
        bb.stop()

    def test_state_fn_error_still_snapshots(self, tmp_path):
        def boom():
            raise RuntimeError("collector died")
        bb = Blackbox(str(tmp_path / "bb"), state_fn=boom,
                      interval_s=60.0)
        snap = bb.snapshot("periodic")
        assert "collector died" in snap["stateError"]
        bb.stop()


# -- WAL flusher health --------------------------------------------------------


class TestWalFlusherHealth:
    def test_dirty_age_tracked_and_cleared(self, tmp_path):
        f = open(tmp_path / "wal", "ab")
        wal = storage_wal.GroupCommitWal(f, fsync_policy="none")
        try:
            wal.append(b"x" * storage_wal.OP_SIZE)
            health = storage_wal.flusher_health()
            mine = [w for w in health["wals"]
                    if w["file"] == f.name]
            assert mine and mine[0]["pendingBytes"] > 0
            assert health["oldestDirtyAgeS"] >= 0.0
            wal.barrier()
            health = storage_wal.flusher_health()
            assert not [w for w in health["wals"]
                        if w["file"] == f.name]
        finally:
            wal.close()
            f.close()


# -- watchdog ------------------------------------------------------------------


def _quiet_sampler(tmp_path=None, disk=None):
    return TailSampler(
        disk=disk, head_n=0, slow_floor_s=30.0,
        histogram=obs_metrics.Histogram(
            "pilosa_test_watchdog_latency_seconds", buckets=(64.0,)))


class TestWatchdog:
    def test_wedged_wal_flusher_trips_and_dump_names_wal(
            self, tmp_path):
        """THE acceptance path: arm a delay failpoint on wal.append
        (the leader flush wedges mid-write, exactly like a hung disk),
        let records go dirty, and the watchdog must trip wal_flusher
        and produce a blackbox dump whose WAL section names the wedged
        WAL file with its pending bytes."""
        bb = Blackbox(str(tmp_path / "bb"),
                      state_fn=lambda: {
                          "wal": storage_wal.flusher_health()},
                      interval_s=60.0, node="n1")
        wd = Watchdog(blackbox=bb, wal_stall_s=0.15,
                      deadline_grace_s=0, gossip_silence_s=0,
                      queue_stall_s=0, retrip_s=60.0)
        f = open(tmp_path / "wedged-wal", "ab")
        wal = storage_wal.GroupCommitWal(f, fsync_policy="none")
        before = obs_metrics.WATCHDOG_TRIPS.labels("wal_flusher").value
        try:
            with failpoints.injected("wal.append", "delay(1.5s)*1"):
                wal.append(b"y" * storage_wal.OP_SIZE)
                # A flush attempt wedges in the delayed leader write;
                # run it in a side thread like the background flusher.
                t = threading.Thread(target=lambda: wal.flush(None),
                                     daemon=True)
                t.start()
                deadline = time.time() + 5.0
                fired = []
                while time.time() < deadline and not fired:
                    time.sleep(0.05)
                    fired = [c for c, _ in wd.check()
                             if c == "wal_flusher"]
                assert fired, storage_wal.flusher_health()
                t.join(timeout=10)
        finally:
            wal.close()
            f.close()
        assert obs_metrics.WATCHDOG_TRIPS.labels(
            "wal_flusher").value == before + 1
        dumps = bb.dumps()
        assert dumps, "watchdog trip produced no blackbox dump"
        with open(dumps[-1]) as fh:
            doc = json.load(fh)
        assert doc["cause"] == "watchdog:wal_flusher"
        wal_state = doc["current"]["wal"]
        named = [w["file"] for w in wal_state["wals"]]
        assert str(tmp_path / "wedged-wal") in named, wal_state
        assert wal_state["oldestDirtyAgeS"] > 0.15
        bb.stop()

    def test_stuck_query_trips_and_force_keeps_trace(self, tmp_path):
        registry = QueryRegistry()
        tracer = Tracer(enabled=False)
        disk = SegmentRing(str(tmp_path / "traces"))
        sampler = _quiet_sampler(disk=disk)
        wd = Watchdog(registry=registry, tracer=tracer,
                      sampler=sampler, wal_stall_s=0,
                      deadline_grace_s=0.05, gossip_silence_s=0,
                      queue_stall_s=0, retrip_s=60.0)
        ctx = QueryContext(pql="Count(...)", timeout_s=0.01)
        registry.register(ctx)
        ctx.state = "running"
        trace = tracer.start(ctx, node="n1")
        with ctx.stage("execute"):
            pass
        time.sleep(0.1)  # now well past deadline + grace
        fired = wd.check()
        assert [c for c, _ in fired] == ["stuck_query"]
        # The in-flight trace was force-kept and persisted.
        assert trace.keep_reason == "watchdog"
        assert any(t["id"] == ctx.id for t in tracer.traces())
        assert any(r["id"] == ctx.id for r in disk.scan())
        registry.finish(ctx)
        disk.close()

    def test_admission_stall_and_gossip_silence(self):
        adm = AdmissionController(concurrency=1, queue_depth=4)
        wd = Watchdog(admission=adm, gossip_age_fn=lambda: 120.0,
                      wal_stall_s=0, deadline_grace_s=0,
                      gossip_silence_s=30.0, queue_stall_s=0.05,
                      retrip_s=60.0)
        slot = adm.acquire("read")
        waiter_in = threading.Event()

        def waiter():
            waiter_in.set()
            s = adm.acquire("read", None)
            s.release()

        t = threading.Thread(target=waiter, daemon=True)
        t.start()
        waiter_in.wait(1)
        time.sleep(0.15)  # queued, no grant for > queue_stall_s
        causes = {c for c, _ in wd.check()}
        assert causes == {"gossip_silence", "admission_stall"}
        # Rate limit: an immediate re-check does not re-trip.
        assert wd.check() == []
        slot.release()
        t.join(timeout=5)

    def test_quiet_system_never_trips(self):
        wd = Watchdog(admission=AdmissionController(),
                      registry=QueryRegistry(),
                      gossip_age_fn=lambda: None)
        assert wd.check() == []
        snap = wd.snapshot()
        assert snap["trips"] == 0


# -- handler routes ------------------------------------------------------------


def _call(app, method, path, body=b""):
    if "?" in path:
        path, _, qs = path.partition("?")
    else:
        qs = ""
    environ = {"REQUEST_METHOD": method, "PATH_INFO": path,
               "QUERY_STRING": qs, "CONTENT_LENGTH": str(len(body)),
               "wsgi.input": io.BytesIO(body)}
    out = {}

    def start_response(status, hs):
        out["status"] = int(status.split()[0])

    chunks = app(environ, start_response)
    return out["status"], b"".join(chunks)


class TestBlackboxRoutes:
    def test_routes(self, tmp_path):
        from pilosa_tpu.server.handler import Handler
        bb = Blackbox(str(tmp_path / "bb"),
                      state_fn=lambda: {"k": 1}, interval_s=60.0)
        bb.snapshot("periodic")
        wd = Watchdog(blackbox=bb, wal_stall_s=0, deadline_grace_s=0,
                      gossip_silence_s=0, queue_stall_s=0)
        h = Handler(None, None, blackbox=bb, watchdog=wd)
        status, body = _call(h, "GET", "/debug/blackbox")
        assert status == 200
        doc = json.loads(body)
        assert doc["enabled"] is True
        assert doc["recent"][0]["k"] == 1
        assert "watchdog" in doc
        status, body = _call(h, "POST", "/debug/blackbox/dump")
        assert status == 200
        assert os.path.exists(json.loads(body)["dumped"])
        bb.stop()

    def test_routes_without_recorder(self):
        from pilosa_tpu.server.handler import Handler
        h = Handler(None, None)
        status, body = _call(h, "GET", "/debug/blackbox")
        assert status == 200
        assert json.loads(body)["enabled"] is False
        status, _ = _call(h, "POST", "/debug/blackbox/dump")
        assert status == 404
