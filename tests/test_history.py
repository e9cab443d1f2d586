"""Fleet observability (ISSUE 13): the on-disk metric history, the
cluster federation merge, the regression sentinel, and their handler
routes — docs/OBSERVABILITY.md is the operator-facing contract.

The chaos legs here drive the ``ring.write`` failpoint through the
HISTORY write site (the acceptance criterion): a torn tick record
costs exactly that tick, reopen serves the pre-kill series minus at
most the unflushed tail."""

import io
import json
import os
import threading
import time

import pytest

from pilosa_tpu.fault import failpoints
from pilosa_tpu.obs import federate
from pilosa_tpu.obs import metrics as obs_metrics
from pilosa_tpu.obs.history import (MetricHistory, series_key,
                                    split_key)
from pilosa_tpu.obs.sentinel import Sentinel, robust_z
from pilosa_tpu.obs.trace import Tracer
from pilosa_tpu.server.handler import Handler


def call(app, method, path, body=b"", headers=None):
    if "?" in path:
        path, _, qs = path.partition("?")
    else:
        qs = ""
    environ = {"REQUEST_METHOD": method, "PATH_INFO": path,
               "QUERY_STRING": qs, "CONTENT_LENGTH": str(len(body)),
               "wsgi.input": io.BytesIO(body)}
    for k, v in (headers or {}).items():
        environ["HTTP_" + k.upper().replace("-", "_")] = v
    out = {}

    def start_response(status, hs):
        out["status"] = int(status.split()[0])
        out["headers"] = dict(hs)

    chunks = app(environ, start_response)
    return out["status"], out["headers"], b"".join(chunks)


RES = ((1.0, 100), (5.0, 40), (25.0, 20))


def _reg_with_families(tag):
    reg = obs_metrics.Registry()
    c = reg.counter(f"pilosa_test_{tag}_events_total", labels=("k",))
    g = reg.gauge(f"pilosa_test_{tag}_depth_value")
    h = reg.histogram(f"pilosa_test_{tag}_lat_seconds",
                      buckets=(0.001, 0.01, 0.1, 1.0))
    return reg, c, g, h


# -- the store -----------------------------------------------------------------


class TestMetricHistory:
    def test_counter_rate_gauge_value_histogram_quantiles(self):
        reg, c, g, h = _reg_with_families("a")
        hist = MetricHistory(resolutions=RES, registry=reg)
        t0 = 1000.0
        for i in range(10):
            c.labels("x").inc(5)
            g.set(i)
            h.observe(0.005)
            h.observe(0.05)
            hist.sample(now=t0 + i)
        out = hist.series("pilosa_test_a_events_total", window_s=60,
                          now=t0 + 10)
        (s,) = out["series"]
        assert s["labels"] == {"k": "x"}
        # 5 increments per 1s tick → rate 5/s (first tick has no
        # previous value, so 9 points).
        assert len(s["points"]) == 9
        assert all(abs(v - 5.0) < 1e-6 for _t, v in s["points"])
        out = hist.series("pilosa_test_a_depth_value", window_s=60,
                          now=t0 + 10)
        assert out["series"][0]["points"][-1][1] == 9.0
        out = hist.series("pilosa_test_a_lat_seconds", window_s=60,
                          now=t0 + 10)
        by_name = {s["name"]: s for s in out["series"]}
        # Two observations per tick, one in each of the first two
        # buckets: p50 = 0.01 bound, p99 = 0.1 bound, rate = 2/s.
        assert by_name["pilosa_test_a_lat_seconds:p50"][
            "points"][-1][1] == pytest.approx(0.01)
        assert by_name["pilosa_test_a_lat_seconds:p99"][
            "points"][-1][1] == pytest.approx(0.1)
        assert by_name["pilosa_test_a_lat_seconds:rate"][
            "points"][-1][1] == pytest.approx(2.0)

    def test_counter_reset_skips_tick_instead_of_negative_rate(self):
        reg, c, _g, _h = _reg_with_families("rst")
        hist = MetricHistory(resolutions=RES, registry=reg)
        child = c.labels("x")
        child.inc(10)
        hist.sample(now=100.0)
        child.inc(10)
        hist.sample(now=101.0)
        child._v = 0.0  # a restart-shaped reset
        hist.sample(now=102.0)
        child.inc(10)
        hist.sample(now=103.0)
        (s,) = hist.series("pilosa_test_rst_events_total",
                           window_s=60, now=104.0)["series"]
        assert all(v >= 0 for _t, v in s["points"]), s["points"]

    def test_base_ring_bounded_and_coarse_aggregates_means(self):
        reg, _c, g, _h = _reg_with_families("b")
        hist = MetricHistory(resolutions=RES, registry=reg)
        t0 = 5000.0
        for i in range(120):  # past the base cap of 100
            g.set(float(i % 10))
            hist.sample(now=t0 + i)
        (s,) = hist.series("pilosa_test_b_depth_value",
                           window_s=99, step_s=0,
                           now=t0 + 120)["series"]
        assert len(s["points"]) <= RES[0][1]
        # Step hint 5s selects the mid ring: bucket means of the
        # 0..9 sawtooth sit strictly inside (0, 9).
        out = hist.series("pilosa_test_b_depth_value", window_s=99,
                          step_s=5.0, now=t0 + 120)
        assert out["stepS"] == 5.0
        (sm,) = out["series"]
        assert sm["points"], sm
        assert all(0.0 < v < 9.0 for _t, v in sm["points"][1:-1])

    def test_resolution_pick_bumps_to_cover_window(self):
        hist = MetricHistory(resolutions=RES)
        assert hist._pick_resolution(30.0, 0.0) == 0
        assert hist._pick_resolution(150.0, 0.0) == 1  # > 1s*100 span
        assert hist._pick_resolution(900.0, 0.0) == 2  # > 5s*40 span
        assert hist._pick_resolution(30.0, 25.0) == 2  # step hint

    def test_series_cap_drops_new_series(self):
        reg = obs_metrics.Registry()
        c = reg.counter("pilosa_test_cap_events_total", labels=("k",))
        hist = MetricHistory(resolutions=RES, registry=reg,
                             max_series=16)
        for i in range(40):
            c.labels(f"k{i}").inc()
        hist.sample(now=100.0)
        for i in range(40):
            c.labels(f"k{i}").inc()
        hist.sample(now=101.0)
        assert len(hist.keys()) <= 16
        assert hist.dropped_series > 0

    def test_label_filter_and_key_round_trip(self):
        key = series_key("pilosa_x_y_total",
                         {"k": 'ho"sti\nle\\', "z": "1"})
        name, labels = split_key(key)
        assert name == "pilosa_x_y_total"
        assert labels == {"k": 'ho"sti\nle\\', "z": "1"}
        reg, c, _g, _h = _reg_with_families("lf")
        hist = MetricHistory(resolutions=RES, registry=reg)
        for k in ("a", "b"):
            c.labels(k).inc()
        hist.sample(now=100.0)
        for k in ("a", "b"):
            c.labels(k).inc()
        hist.sample(now=101.0)
        out = hist.series("pilosa_test_lf_events_total",
                          label_filter={"k": "a"}, window_s=60,
                          now=102.0)
        assert len(out["series"]) == 1
        assert out["series"][0]["labels"] == {"k": "a"}

    def test_resolution_ladder_validated_at_load(self):
        """parse_resolutions is the load-time gate: the store
        hard-depends on a strictly-ascending finest-first ladder, so
        a misordered or degenerate env value fails loudly instead of
        serving garbage history (review finding)."""
        from pilosa_tpu.utils.config import parse_resolutions
        assert parse_resolutions("10s:360,1m:720") == ((10.0, 360),
                                                       (60.0, 720))
        for bad in ("1m:720,10s:360",   # descending
                    "10s:0",            # zero capacity
                    "10s:360,10s:100",  # duplicate step
                    ""):
            with pytest.raises(ValueError):
                parse_resolutions(bad)

    def test_double_sample_same_tick_is_ignored(self):
        reg, _c, g, _h = _reg_with_families("ds")
        hist = MetricHistory(resolutions=RES, registry=reg)
        g.set(1)
        assert hist.sample(now=100.0) > 0
        # The on-demand /status path re-entering inside half a step.
        assert hist.sample(now=100.2) == 0
        assert hist.sample(now=101.0) > 0

    def test_persistence_reopen_serves_series(self, tmp_path):
        reg, c, _g, _h = _reg_with_families("p")
        d = str(tmp_path / "hist")
        hist = MetricHistory(d, resolutions=RES, registry=reg)
        t0 = 100.0
        for i in range(20):
            c.labels("x").inc(3)
            hist.sample(now=t0 + i)
        before = hist.series("pilosa_test_p_events_total",
                             window_s=60, now=t0 + 20)["series"]
        hist.close()
        re = MetricHistory(d, resolutions=RES, registry=reg)
        after = re.series("pilosa_test_p_events_total", window_s=60,
                          now=t0 + 20)["series"]
        assert after == before
        re.close()

    def test_coarse_replay_keeps_bucket_timestamps(self, tmp_path):
        """Coarse flushes persist as [bucket_start, mean] pairs:
        replayed 5s/25s points must carry the SAME timestamps as the
        in-memory ring did (a flush-time stamp would shift every
        coarse point one step late across a restart — review
        finding)."""
        reg, _c, g, _h = _reg_with_families("cr")
        d = str(tmp_path / "hist")
        hist = MetricHistory(d, resolutions=RES, registry=reg)
        t0 = 10000.0
        for i in range(60):   # enough to flush several 5s buckets
            g.set(float(i))
            hist.sample(now=t0 + i)
        before = hist.series("pilosa_test_cr_depth_value",
                             window_s=99, step_s=5.0,
                             now=t0 + 60)["series"]
        hist.close()
        re = MetricHistory(d, resolutions=RES, registry=reg)
        after = re.series("pilosa_test_cr_depth_value", window_s=99,
                          step_s=5.0, now=t0 + 60)["series"]
        assert after == before
        # Bucket-aligned: every coarse timestamp sits on a 5s edge.
        assert all(t % 5.0 == 0 for t, _v in after[0]["points"])
        re.close()

    def test_sigkill_shaped_torn_tail_serves_prefix(self, tmp_path):
        """A half-written tick record on disk (SIGKILL mid-write(2)):
        reopen serves every whole tick and silently skips the torn
        tail — the acceptance shape."""
        reg, c, _g, _h = _reg_with_families("k9")
        d = str(tmp_path / "hist")
        hist = MetricHistory(d, resolutions=RES, registry=reg)
        for i in range(10):
            c.labels("x").inc(2)
            hist.sample(now=100.0 + i)
        hist.close()
        seg_dir = os.path.join(d, "res0")
        seg = sorted(os.listdir(seg_dir))[-1]
        with open(os.path.join(seg_dir, seg), "ab") as f:
            f.write(b'deadbeef {"t": 110.0, "s": {"trunca')
        re = MetricHistory(d, resolutions=RES, registry=reg)
        (s,) = re.series("pilosa_test_k9_events_total", window_s=60,
                         now=110.0)["series"]
        assert len(s["points"]) == 9  # all whole ticks, tail gone
        re.close()

    def test_failpoint_torn_write_at_history_site(self, tmp_path):
        """The chaos acceptance: the ring.write failpoint tears a
        history tick mid-record. That tick's persistence is lost (the
        in-memory ring keeps it), later ticks persist into a fresh
        segment, and reopen serves pre-tear + post-tear ticks."""
        reg, c, _g, _h = _reg_with_families("fp")
        d = str(tmp_path / "hist")
        hist = MetricHistory(d, resolutions=RES, registry=reg)
        for i in range(5):
            c.labels("x").inc(2)
            hist.sample(now=100.0 + i)
        dropped_before = hist.disk[0].dropped
        with failpoints.injected("ring.write", "torn(9)*1"):
            c.labels("x").inc(2)
            hist.sample(now=105.0)
        assert hist.disk[0].dropped == dropped_before + 1
        for i in range(3):
            c.labels("x").inc(2)
            hist.sample(now=106.0 + i)
        hist.close()
        re = MetricHistory(d, resolutions=RES, registry=reg)
        (s,) = re.series("pilosa_test_fp_events_total", window_s=60,
                         now=110.0)["series"]
        ts = [t for t, _v in s["points"]]
        # The torn tick (105) is the at-most-one lost record; ticks
        # before and after it all serve.
        assert 105.0 not in ts
        assert {101.0, 102.0, 103.0, 104.0, 106.0, 107.0,
                108.0} <= set(ts), ts
        re.close()


# -- the federation merge ------------------------------------------------------


class TestFederate:
    def _node_text(self, events=3, depth=5.0, obs=(0.05,)):
        reg = obs_metrics.Registry()
        reg.counter("pilosa_test_m_events_total").inc(events)
        reg.gauge("pilosa_test_m_depth_value").set(depth)
        h = reg.histogram("pilosa_test_m_lat_seconds",
                          buckets=(0.1, 1.0))
        for v in obs:
            h.observe(v)
        return reg.render()

    def test_counters_sum_gauges_pernode_histograms_merge(self):
        per_node = {
            "n1:1": federate.parse_exposition(self._node_text(3, 5.0)),
            "n2:1": federate.parse_exposition(
                self._node_text(4, 7.0, obs=(0.5, 5.0))),
        }
        merged = federate.merge_node_families(per_node)
        text = federate.render_merged(merged)
        fams = federate.parse_exposition(text)
        (_, _, total), = fams["pilosa_test_m_events_total"]["samples"]
        assert total == 7.0
        depths = {labels["node"]: v for _n, labels, v in
                  fams["pilosa_test_m_depth_value"]["samples"]}
        assert depths == {"n1:1": 5.0, "n2:1": 7.0}
        hs = {(n, labels.get("le")): v for n, labels, v in
              fams["pilosa_test_m_lat_seconds"]["samples"]}
        assert hs[("pilosa_test_m_lat_seconds_bucket", "0.1")] == 1.0
        assert hs[("pilosa_test_m_lat_seconds_bucket", "+Inf")] == 3.0
        assert hs[("pilosa_test_m_lat_seconds_count", None)] == 3.0

    def test_merged_output_reparses_with_test_parser(self):
        import sys
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from test_obs import parse_exposition as strict_parse
        per_node = {"a:1": federate.parse_exposition(
            self._node_text())}
        text = federate.render_merged(
            federate.merge_node_families(per_node))
        fams = strict_parse(text)
        assert "pilosa_test_m_events_total" in fams

    def test_help_text_round_trips_without_double_escape(self):
        """parse_exposition unescapes HELP so render_merged's
        re-escape yields the identical wire form per federation hop
        (a still-escaped stored form would double backslashes on
        every hop — review finding)."""
        reg = obs_metrics.Registry()
        reg.counter("pilosa_test_mh_events_total",
                    "back\\slash and\nnewline")
        text = reg.render()
        one_hop = federate.render_merged(federate.merge_node_families(
            {"n1": federate.parse_exposition(text)}))
        two_hop = federate.render_merged(federate.merge_node_families(
            {"n1": federate.parse_exposition(one_hop)}))
        help1 = next(ln for ln in one_hop.splitlines()
                     if ln.startswith("# HELP"))
        help2 = next(ln for ln in two_hop.splitlines()
                     if ln.startswith("# HELP"))
        assert help1 == help2
        assert "back\\\\slash and\\nnewline" in help1

    def test_fan_out_reports_unreachable_peers(self):
        class Node:
            def __init__(self, host):
                self.host = host

        class Cluster:
            nodes = [Node("me:1"), Node("up:1"), Node("down:1")]

        fed = federate.Federator("me:1", cluster=Cluster())

        def fetch(host):
            if host == "down:1":
                raise OSError("connection refused")
            return {"host": host}

        results, missing = fed.fan_out(fetch, lambda: {"host": "me:1"})
        assert set(results) == {"me:1", "up:1"}
        assert missing == ["down:1"]


# -- the sentinel ---------------------------------------------------------------


class _FakeBlackbox:
    def __init__(self):
        self.snaps = []

    def snapshot(self, trigger, extra=None):
        self.snaps.append((trigger, extra))
        return {}


def _hist_with_cliff(tag, baseline_v=0.005, cliff_v=0.5,
                     n_base=100, n_cliff=15):
    reg = obs_metrics.Registry()
    h = reg.histogram(f"pilosa_{tag}_q_seconds",
                      buckets=(0.001, 0.01, 0.1, 1.0))
    hist = MetricHistory(resolutions=((1.0, 4000), (5.0, 50),
                                      (25.0, 20)), registry=reg)
    now = 10000.0
    for _ in range(n_base):
        h.observe(baseline_v)
        hist.sample(now=now)
        now += 1
    for _ in range(n_cliff):
        h.observe(cliff_v)
        hist.sample(now=now)
        now += 1
    return hist, now, f"pilosa_{tag}_q_seconds"


class TestSentinel:
    def test_robust_z_math(self):
        z, rm, bm = robust_z([10.0] * 5, [1.0, 1.1, 0.9, 1.0, 1.05])
        assert rm == 10.0 and bm == pytest.approx(1.0)
        assert z > 50
        z2, _, _ = robust_z([1.0] * 5, [1.0, 1.1, 0.9, 1.0, 1.05])
        assert abs(z2) < 1

    def test_latency_cliff_fires_up_finding(self):
        hist, now, fam = _hist_with_cliff("sent1")
        bb = _FakeBlackbox()
        s = Sentinel(hist, blackbox=bb, window_s=10, baseline_s=200,
                     min_points=3, zscore=4.0,
                     watches=((f"{fam}:p99", "up"),))
        fired = s.check(now=now)
        assert fired and fired[0]["direction"] == "up"
        assert fired[0]["metric"] == f"{fam}:p99"
        # The blackbox snapshot names the regressed metric.
        trigger, extra = bb.snaps[0]
        assert trigger == "sentinel"
        assert extra["sentinel"]["metric"] == f"{fam}:p99"
        # Counter + active gauge raised.
        assert obs_metrics.SENTINEL_FINDINGS.labels(
            f"{fam}:p99", "up").value >= 1
        assert obs_metrics.SENTINEL_ACTIVE.labels(
            f"{fam}:p99", "up").value == 1

    def test_rate_collapse_fires_down_finding(self):
        reg = obs_metrics.Registry()
        c = reg.counter("pilosa_sent2_q_total")
        hist = MetricHistory(resolutions=((1.0, 4000), (5.0, 50),
                                          (25.0, 20)), registry=reg)
        now = 10000.0
        for _ in range(100):
            c.inc(50)
            hist.sample(now=now)
            now += 1
        for _ in range(15):
            c.inc(1)   # the traffic cliff
            hist.sample(now=now)
            now += 1
        s = Sentinel(hist, window_s=10, baseline_s=200, min_points=3,
                     zscore=4.0,
                     watches=(("pilosa_sent2_q_total", "down"),))
        fired = s.check(now=now)
        assert fired and fired[0]["direction"] == "down", fired

    def test_small_shift_below_min_ratio_does_not_fire(self):
        hist, now, fam = _hist_with_cliff("sent3", baseline_v=0.005,
                                          cliff_v=0.007)
        s = Sentinel(hist, window_s=10, baseline_s=200, min_points=3,
                     zscore=4.0, min_ratio=1.5,
                     watches=((f"{fam}:p50", "up"),))
        assert s.check(now=now) == []

    def test_refire_rate_limited_and_recovery_clears_active(self):
        hist, now, fam = _hist_with_cliff("sent4")
        s = Sentinel(hist, window_s=10, baseline_s=200, min_points=3,
                     zscore=4.0, retrip_s=300,
                     watches=((f"{fam}:p99", "up"),))
        assert s.check(now=now)
        assert s.check(now=now + 5) == []     # inside retrip
        # Let the series recover: feed baseline-speed ticks until the
        # recent window is healthy again.
        reg_h = hist.registry.families()[fam]
        for i in range(15):
            reg_h.observe(0.005)
            hist.sample(now=now + 10 + i)
        assert s.check(now=now + 25) == []
        assert obs_metrics.SENTINEL_ACTIVE.labels(
            f"{fam}:p99", "up").value == 0

    def test_manifest_envelope_rule(self, tmp_path):
        reg = obs_metrics.Registry()
        h = reg.histogram("pilosa_query_duration_seconds",
                          labels=("call", "lane", "status"),
                          buckets=(0.001, 0.01, 0.1, 1.0, 10.0))
        hist = MetricHistory(resolutions=((1.0, 400), (5.0, 50),
                                          (25.0, 20)), registry=reg)
        now = 10000.0
        for _ in range(20):
            h.labels("Count", "read", "200").observe(0.5)  # very slow
            hist.sample(now=now)
            now += 1
        manifest = tmp_path / "MANIFEST.json"
        manifest.write_text(json.dumps({"metrics": {
            "latency_below_cap_p99": {"value": 17.7, "unit": "ms"}}}))
        s = Sentinel(hist, window_s=10, baseline_s=200, min_points=3,
                     zscore=1e9,   # silence the z rules
                     manifest_path=str(manifest),
                     manifest_tolerance=5.0, watches=())
        fired = s.check(now=now)
        assert fired, fired
        assert fired[0]["rule"] == "manifest"
        assert fired[0]["manifestKey"] == "latency_below_cap_p99"
        # 0.5s recent median vs 17.7ms * 5 = 88.5ms bound.
        assert fired[0]["recentMedian"] > fired[0]["committed"]

    def test_finding_force_keeps_inflight_trace_as_anomaly(
            self, tmp_path):
        from pilosa_tpu.obs.diskring import SegmentRing
        from pilosa_tpu.obs.sampler import TailSampler
        from pilosa_tpu.sched import QueryContext, QueryRegistry
        hist, now, fam = _hist_with_cliff("sent5")
        tracer = Tracer(enabled=False)
        sampler = TailSampler(disk=SegmentRing(str(tmp_path / "tr")))
        registry = QueryRegistry()
        ctx = QueryContext(pql="Count(...)", index="i", lane="read")
        trace = tracer.start(ctx, node="n1")
        registry.register(ctx)
        try:
            s = Sentinel(hist, registry=registry, tracer=tracer,
                         sampler=sampler, window_s=10, baseline_s=200,
                         min_points=3, zscore=4.0,
                         watches=((f"{fam}:p99", "up"),))
            assert s.check(now=now)
        finally:
            registry.finish(ctx)
        assert trace.keep_reason == "anomaly"
        ring = tracer.traces()
        assert any(t["id"] == ctx.id and t["reason"] == "anomaly"
                   for t in ring), ring
        disk = [r for r in sampler.disk.scan()
                if r.get("id") == ctx.id]
        assert disk and disk[0]["reason"] == "anomaly"
        sampler.disk.close()


# -- handler routes -------------------------------------------------------------


class TestFleetHandler:
    def _handler(self, tmp_path=None, history=None, sentinel=None,
                 federator=None, sampler=None):
        return Handler(None, None, host="local",
                       tracer=Tracer(enabled=False), history=history,
                       sentinel=sentinel, federator=federator,
                       sampler=sampler)

    def test_history_route_params_and_series(self):
        reg, c, _g, _h = _reg_with_families("hr")
        hist = MetricHistory(resolutions=RES, registry=reg)
        t0 = time.time() - 10   # the route queries against wall-clock
        for i in range(5):
            c.labels("x").inc()
            hist.sample(now=t0 + i)
        handler = self._handler(history=hist)
        st, _hd, body = call(
            handler, "GET",
            "/debug/metrics/history?family=pilosa_test_hr_events_total"
            "&window=90s&label=k=x")
        assert st == 200
        doc = json.loads(body)
        assert doc["series"] and doc["series"][0]["labels"] == {
            "k": "x"}
        st, _hd, _body = call(handler, "GET",
                              "/debug/metrics/history?window=bogus")
        assert st == 400
        st, _hd, _body = call(handler, "GET",
                              "/debug/metrics/history?label=bogus")
        assert st == 400
        # No history wired: an empty, marked answer — not a 500.
        st, _hd, body = call(self._handler(), "GET",
                             "/debug/metrics/history")
        assert st == 200
        assert json.loads(body)["enabled"] is False

    def test_metrics_cluster_single_node_marks_gauges(self):
        obs_metrics.HISTORY_SERIES_LIVE.set(3)
        obs_metrics.HISTORY_SAMPLES.inc(0)
        handler = self._handler()
        st, hd, body = call(handler, "GET", "/metrics/cluster")
        assert st == 200
        assert hd["X-Pilosa-Federated-Nodes"] == "1"
        fams = federate.parse_exposition(body.decode())
        # Gauges carry the node label; counters stay plain.
        g = fams.get("pilosa_history_series_live")
        assert g and all(labels.get("node") == "local"
                         for _n, labels, _v in g["samples"])
        c = fams.get("pilosa_history_samples_total")
        assert c and all("node" not in labels
                         for _n, labels, _v in c["samples"])

    def test_partial_contract_503_then_marked(self):
        class Node:
            def __init__(self, host):
                self.host = host

        class Cluster:
            nodes = [Node("local"), Node("gone:1")]

        class DeadClient:
            def metrics_text(self, host=None, deadline_s=None):
                raise OSError("connection refused")

            def debug_cluster_local(self, host=None, deadline_s=None):
                raise OSError("connection refused")

        fed = federate.Federator("local", cluster=Cluster(),
                                 client_for=lambda h: DeadClient())
        handler = self._handler(federator=fed)
        st, _hd, body = call(handler, "GET", "/metrics/cluster")
        assert st == 503 and b"gone:1" in body
        st, hd, _body = call(handler, "GET",
                             "/metrics/cluster?partial=1")
        assert st == 200
        assert hd["X-Pilosa-Partial-Nodes"] == "gone:1"
        st, hd, body = call(handler, "GET",
                            "/debug/cluster?partial=1")
        assert st == 200
        doc = json.loads(body)
        assert doc["missing"] == ["gone:1"]
        assert "local" in doc["nodes"]

    def test_debug_cluster_rollup_and_version_skew(self):
        handler = self._handler()
        st, _hd, body = call(handler, "GET", "/debug/cluster?local=1")
        assert st == 200
        block = json.loads(body)
        assert block["build"]["version"]
        st, _hd, body = call(handler, "GET", "/debug/cluster")
        doc = json.loads(body)
        assert doc["coordinator"] == "local"
        assert doc["versionSkew"] is False
        assert doc["versions"]["local"] == block["build"]["version"]

    def test_sentinel_route(self):
        hist = MetricHistory(resolutions=RES)
        s = Sentinel(hist, interval_s=999)
        handler = self._handler(sentinel=s)
        st, _hd, body = call(handler, "GET", "/debug/sentinel")
        assert st == 200
        doc = json.loads(body)
        assert doc["enabled"] is True and "findings" in doc
        st, _hd, body = call(self._handler(), "GET", "/debug/sentinel")
        assert json.loads(body)["enabled"] is False

    def test_traces_pagination_and_summary(self, tmp_path):
        from pilosa_tpu.obs.diskring import SegmentRing
        from pilosa_tpu.obs.sampler import TailSampler, trace_record
        from pilosa_tpu.obs.trace import Trace
        tracer = Tracer(enabled=False, max_traces=64)
        disk = SegmentRing(str(tmp_path / "tr"))
        sampler = TailSampler(disk=disk)
        for i in range(10):
            t = Trace(f"q{i}", node="n1")
            reason = "slow" if i % 2 else "error"
            tracer.keep(t, reason=reason)
            disk.append(trace_record(t, reason))
        handler = Handler(None, None, host="local", tracer=tracer,
                          sampler=sampler)
        st, _hd, body = call(handler, "GET",
                             "/debug/traces?limit=3&offset=0")
        page1 = json.loads(body)
        st, _hd, body = call(handler, "GET",
                             "/debug/traces?limit=3&offset=3")
        page2 = json.loads(body)
        assert page1["total"] == page2["total"] == 10
        ids1 = [t["id"] for t in page1["traces"]]
        ids2 = [t["id"] for t in page2["traces"]]
        assert len(ids1) == len(ids2) == 3
        assert not set(ids1) & set(ids2)
        # Disk source pages the same way, filtered by reason.
        st, _hd, body = call(
            handler, "GET",
            "/debug/traces?source=disk&reason=slow&limit=2&offset=2")
        doc = json.loads(body)
        assert doc["total"] == 5 and len(doc["traces"]) == 2
        assert all(t["reason"] == "slow" for t in doc["traces"])
        # The reason-count rollup over both stores.
        st, _hd, body = call(handler, "GET", "/debug/traces/summary")
        doc = json.loads(body)
        assert doc["ring"] == {"slow": 5, "error": 5}
        assert doc["disk"] == {"slow": 5, "error": 5}
        disk.close()


# -- sentinel end-to-end: a failpoint latency cliff on a hot path --------------


class TestSentinelEndToEnd:
    def test_injected_latency_cliff_raises_finding_keeps_trace(
            self, tmp_path):
        """The acceptance path: real handler + holder + executor; a
        wal.append failpoint delay turns the write path into a cliff;
        the sentinel (fed by real QUERY_SECONDS observations through
        the history) raises pilosa_sentinel_findings, force-keeps an
        in-flight trace under reason ``anomaly``, and lands a
        blackbox snapshot naming the regressed metric."""
        from pilosa_tpu.executor import Executor
        from pilosa_tpu.models.holder import Holder
        from pilosa_tpu.obs.blackbox import Blackbox
        from pilosa_tpu.obs.diskring import SegmentRing
        from pilosa_tpu.obs.sampler import TailSampler

        holder = Holder(str(tmp_path / "data"))
        holder.open()
        holder.create_index_if_not_exists("i") \
            .create_frame_if_not_exists("f")
        ex = Executor(holder, host="local")
        sampler = TailSampler(
            disk=SegmentRing(str(tmp_path / "traces")),
            head_n=0, slow_floor_s=60.0)
        handler = Handler(holder, ex, host="local",
                          tracer=Tracer(enabled=False),
                          sampler=sampler)
        hist = MetricHistory(resolutions=((1.0, 4000), (5.0, 50),
                                          (25.0, 20)))
        blackbox = Blackbox(str(tmp_path / "bb"),
                            state_fn=lambda: {"ok": True},
                            interval_s=3600, node="local")
        # min_ratio 3: write timings jitter across adjacent
        # power-of-2 histogram buckets (a 2x "shift"); the injected
        # cliff, 2 ms to 60 ms, is four buckets up (16x), so the rule
        # still fires loudly.
        sentinel = Sentinel(
            hist, registry=handler.registry, tracer=handler.tracer,
            sampler=sampler, blackbox=blackbox, interval_s=3600,
            window_s=10, baseline_s=300, min_points=3, zscore=4.0,
            min_ratio=3.0)

        def write(n):
            st, _hd, _b = call(
                handler, "POST", "/index/i/query",
                f'SetBit(rowID=1, frame="f", columnID={n})'.encode())
            assert st == 200

        # The history samples the process-wide registry, which holds
        # what earlier tests of this worker left in it. Counters and
        # histograms enter as deltas between ticks, but a gauge enters
        # as its value: a tenant burn rate that a server test left
        # high would be an (honest) finding of the quiet baseline.
        for _labels, child in obs_metrics.TENANT_SLO_BURN._label_dicts():
            child.set(0.0)
        # Baseline: one history tick per (fake) second. Both levels of
        # the step come from failpoints, 2 ms here and 60 ms below: a
        # bare write sits in the histogram's first bucket (1 ms), where
        # a loaded machine's 2 ms stalls alone are a 3x step.
        now = time.time()
        col = 0
        with failpoints.injected("wal.append", "delay(2ms)"):
            for _ in range(100):
                write(col)
                col += 1
                hist.sample(now=now)
                now += 1
        assert sentinel.check(now=now) == []
        # The cliff: every WAL append pays an injected 60ms delay.
        with failpoints.injected("wal.append", "delay(60ms)"):
            for _ in range(12):
                write(col)
                col += 1
                hist.sample(now=now)
                now += 1
            # One query held in flight across the sentinel pass: the
            # evidence the force-keep must capture.
            release = threading.Event()
            started = threading.Event()

            def slow_query():
                started.set()
                release.wait(10)
                write(10**6)

            t = threading.Thread(target=slow_query)
            # Deterministic in-flight context: register it by hand
            # (the thread itself may not reach the handler before the
            # check below).
            from pilosa_tpu.sched import QueryContext
            ctx = QueryContext(pql="SetBit(...)", index="i",
                               lane="write")
            trace = handler.tracer.start(ctx, node="local")
            handler.registry.register(ctx)
            t.start()
            started.wait(5)
            try:
                fired = sentinel.check(now=now)
            finally:
                release.set()
                t.join(15)
                handler.registry.finish(ctx)
        assert fired, fired
        metrics_hit = {f["metric"] for f in fired}
        assert any(m.startswith("pilosa_query_duration_seconds")
                   for m in metrics_hit), metrics_hit
        # The in-flight trace was force-kept under ``anomaly``, in
        # the ring AND on disk.
        assert trace.keep_reason == "anomaly"
        disk = [r for r in sampler.disk.scan()
                if r.get("id") == ctx.id]
        assert disk and disk[0]["reason"] == "anomaly"
        # The blackbox snapshot names the regressed metric.
        snaps = [r for r in blackbox.ring.scan()
                 if r.get("trigger") == "sentinel"]
        assert snaps, "no sentinel snapshot landed"
        named = {s["sentinel"]["metric"] for s in snaps}
        assert any(m.startswith("pilosa_query_duration_seconds")
                   for m in named), named
        sampler.disk.close()
        hist.close()
        ex.close()
        holder.close()
