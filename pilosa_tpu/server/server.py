"""Server runtime: the composition root.

Reference: server.go. Binds the listener (``:0`` supported), opens the
Holder, wires Cluster/Broadcaster/Executor/Handler, serves HTTP on a
threading WSGI server, and runs the background loops: anti-entropy
(server.go:182-214), max-slice polling of peers (server.go:216-252), and
the 1-minute cache flush (holder.go:324-358). ``receive_message`` applies
the five schema-mutation broadcasts (server.go:255-300).
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Optional

from ..cluster.broadcast import (NOP_BROADCASTER, CancelQueryMessage,
                                 ResizeMessage, StaticNodeSet)
from ..cluster import resize as resize_mod
from ..cluster.client import Client
from ..cluster.topology import (NODE_STATE_DOWN, NODE_STATE_UP, Cluster,
                                Node)
from ..errors import PilosaError
from ..executor import Executor
from ..fault import FaultManager
from ..fault import failpoints as fault_failpoints
from ..models.frame import FrameOptions
from ..models.holder import Holder
from ..models.index import IndexOptions
from ..obs import accounting as obs_accounting
from ..obs import blackbox as obs_blackbox
from ..obs.federate import Federator
from ..obs.history import MetricHistory
from ..obs.metrics import RegistryStatsClient, default_registry
from ..obs.profile import ContinuousProfiler
from ..obs.runtime import RuntimeCollector, build_info
from ..obs.sampler import TailSampler
from ..obs.sentinel import Sentinel
from ..obs.slo import SLOTracker, TenantSLOTracker
from ..obs.trace import Tracer
from ..obs.watchdog import Watchdog
from ..proto import internal_pb2 as pb
from ..sched import (AdmissionController, QueryRegistry, TenantRegistry,
                     Warmup, warmup_enabled)
from ..utils import logger as logger_mod
from ..storage.scrub import Scrubber
from ..tier.manager import TierManager
from ..utils.config import (BlackboxConfig, CaptureConfig, FaultConfig,
                            HistoryConfig, MetricsConfig, ProfileConfig,
                            QueryConfig, ScrubConfig, SentinelConfig,
                            SLOConfig, TenantsConfig, TierConfig,
                            TraceConfig, WatchdogConfig,
                            parse_resolutions)
from ..utils.stats import NOP, MultiStatsClient
from .handler import Handler
from .httpd import HTTPServer
from .repair import Repairer

DEFAULT_ANTI_ENTROPY_INTERVAL = 600.0   # seconds (server.go:37)
DEFAULT_POLLING_INTERVAL = 60.0         # max-slice poll (server.go:33)
CACHE_FLUSH_INTERVAL = 60.0             # holder.go:31


class Server:
    """One pilosa-tpu node."""

    def __init__(self, data_dir: str, host: str = "localhost:10101",
                 cluster: Optional[Cluster] = None, broadcaster=None,
                 broadcast_receiver=None, stats=NOP,
                 anti_entropy_interval: float
                 = DEFAULT_ANTI_ENTROPY_INTERVAL,
                 polling_interval: float = DEFAULT_POLLING_INTERVAL,
                 logger=logger_mod.NOP,
                 query_config: Optional[QueryConfig] = None,
                 metrics_config: Optional[MetricsConfig] = None,
                 trace_config: Optional[TraceConfig] = None,
                 profile_config: Optional[ProfileConfig] = None,
                 slo_config: Optional[SLOConfig] = None,
                 fault_config: Optional[FaultConfig] = None,
                 gen_staleness_s: Optional[float] = None,
                 blackbox_config: Optional[BlackboxConfig] = None,
                 watchdog_config: Optional[WatchdogConfig] = None,
                 resize_pace_s: float = 0.0,
                 resize_grace_s: float = 30.0,
                 history_config: Optional[HistoryConfig] = None,
                 sentinel_config: Optional[SentinelConfig] = None,
                 tenants_config: Optional[TenantsConfig] = None,
                 scrub_config: Optional[ScrubConfig] = None,
                 tier_config: Optional[TierConfig] = None,
                 capture_config: Optional[CaptureConfig] = None,
                 backup_config=None):
        self.data_dir = data_dir
        self.host = host
        self.logger = logger
        self.cluster = cluster or Cluster(
            nodes=[Node(host)], node_set=StaticNodeSet([Node(host)]))
        self.broadcaster = broadcaster or NOP_BROADCASTER
        self.broadcast_receiver = broadcast_receiver
        # Observability (obs subsystem; docs/OBSERVABILITY.md): when
        # metrics are on, every legacy StatsClient call site also
        # feeds the process Prometheus registry (/metrics) through the
        # bridge — one call site, every backend.
        self.metrics_config = metrics_config or MetricsConfig()
        self.trace_config = trace_config or TraceConfig()
        if self.metrics_config.enabled:
            stats = MultiStatsClient(
                [stats, RegistryStatsClient(default_registry())])
        self.stats = stats
        self.tracer = Tracer(enabled=self.trace_config.enabled,
                             max_traces=self.trace_config.max_traces,
                             max_spans=self.trace_config.max_spans)
        # Tail sampling + flight recorder + stall watchdog (obs
        # subsystem, docs/OBSERVABILITY.md): built in open() — the
        # disk rings live under the holder data dir.
        self.blackbox_config = blackbox_config or BlackboxConfig()
        self.watchdog_config = watchdog_config or WatchdogConfig()
        self.sampler: Optional[TailSampler] = None
        self.blackbox: Optional[obs_blackbox.Blackbox] = None
        self.watchdog: Optional[Watchdog] = None
        # Fleet observability (this PR; docs/OBSERVABILITY.md): the
        # on-disk metric history, the cluster federator behind
        # /metrics/cluster + /debug/cluster, and the regression
        # sentinel — built in open() (the history ring lives under
        # the holder data dir).
        self.history_config = history_config or HistoryConfig()
        self.sentinel_config = sentinel_config or SentinelConfig()
        self.history: Optional[MetricHistory] = None
        self.sentinel: Optional[Sentinel] = None
        self.federator: Optional[Federator] = None
        # Peer build identities learned via the gossip push/pull
        # piggyback (build_wire_state): version skew across a
        # mixed-version fleet stays visible through /debug/cluster
        # even for nodes a scrape can't reach right now.
        self.peer_builds: dict[str, dict] = {}
        # Continuous profiler + SLO tracker (obs subsystem). The
        # accounting knob stays PER SERVER (threaded into the handler
        # and the batch lane) — a process-global flip here would let
        # the last-constructed in-process server decide accounting for
        # every other one.
        self.profile_config = profile_config or ProfileConfig()
        self.profiler = ContinuousProfiler(
            hz=self.profile_config.hz, ring=self.profile_config.ring)
        self.slo_config = slo_config or SLOConfig()
        self.slo: Optional[SLOTracker] = None
        if self.metrics_config.enabled:
            self.slo = SLOTracker(
                objective_s=self.slo_config.objective,
                target=self.slo_config.target)
        self.runtime: Optional[RuntimeCollector] = None
        self.anti_entropy_interval = anti_entropy_interval
        self.polling_interval = polling_interval

        # Fault-tolerance subsystem (fault; docs/FAULT_TOLERANCE.md):
        # per-peer health EWMA + circuit breakers shared by the
        # executor's placement, every pooled Client, the anti-entropy
        # syncer, and the gossip liveness callback. Disabled =
        # None everywhere, the pre-fault behavior.
        self.fault_config = fault_config or FaultConfig()
        self.fault: Optional[FaultManager] = None
        if self.fault_config.enabled:
            self.fault = FaultManager(
                breaker_threshold=self.fault_config.breaker_threshold,
                backoff_base_s=self.fault_config.breaker_backoff,
                backoff_cap_s=self.fault_config.breaker_backoff_cap,
                hedge_s=self.fault_config.hedge, node=host)

        # Cluster-wide generation knowledge (cluster.generations;
        # docs/DISTRIBUTED.md): every pooled Client feeds peers'
        # piggybacked X-Pilosa-Generations tokens here, and the
        # executor's result caches key + validate remote slices
        # against it.
        from ..cluster.generations import (DEFAULT_STALENESS_S,
                                           GenerationMap)
        self.gens = GenerationMap(
            staleness_s=(gen_staleness_s if gen_staleness_s is not None
                         else DEFAULT_STALENESS_S))

        # Query lifecycle subsystem (sched; docs/SCHEDULING.md): the
        # weighted admission queue in front of the executor — with the
        # tenant (= index) as a second stride level (sched.tenants:
        # weights, caps, quotas, cost-kill ceilings, penalty box) —
        # the in-flight registry behind /debug/queries, and (from
        # open()) the cold-start warmup lane.
        self.query_config = query_config or QueryConfig()
        self.tenants_config = tenants_config or TenantsConfig()
        self.tenants = TenantRegistry(self.tenants_config.table,
                                      node=host)
        # Cluster-wide kill fan-out: reads self.broadcaster at CALL
        # time (it is swapped after open() for http/gossip modes).
        self.tenants.kill_broadcast = self._broadcast_kill
        self.admission = AdmissionController(
            concurrency=self.query_config.concurrency,
            queue_depth=self.query_config.queue_depth,
            tenants=self.tenants)
        # Per-tenant SLO burn (obs.slo.TenantSLOTracker), recorded on
        # the runtime collector's cadence against the SAME objective
        # as the aggregate tracker.
        self.tenant_slo: Optional[TenantSLOTracker] = None
        if self.metrics_config.enabled:
            self.tenant_slo = TenantSLOTracker(
                objective_s=self.slo_config.objective,
                target=self.slo_config.target)
        self.query_registry = QueryRegistry(
            slow_threshold_s=self.query_config.slow_threshold or None,
            stats=stats, logger=logger)
        self.warmup: Optional[Warmup] = None

        self.holder = Holder(data_dir, on_create_slice=self._on_create_slice,
                             stats=stats, logger=logger)
        # Storage integrity (storage.scrub / server.repair;
        # docs/FAULT_TOLERANCE.md): the background scrubber
        # re-verifying on-disk checksums and the repairer re-streaming
        # quarantined fragments from replicas — built in open().
        self.scrub_config = scrub_config or ScrubConfig()
        self.scrubber: Optional[Scrubber] = None
        self.repairer: Optional[Repairer] = None
        # Tiered storage (pilosa_tpu.tier; docs/STORAGE.md): the
        # working-set manager serving indexes bigger than RAM — built
        # in open() when [tier] enables it (the cold dir lives under
        # the data dir by default).
        self.tier_config = tier_config or TierConfig()
        self.tier: Optional[TierManager] = None
        # Workload capture (obs.capture; docs/OBSERVABILITY.md): the
        # recorded-traffic ring behind /debug/capture* — built in
        # open() (the segment ring lives under the data dir).
        self.capture_config = capture_config or CaptureConfig()
        self.capture = None
        # Disaster recovery (pilosa_tpu.backup;
        # docs/DISASTER_RECOVERY.md): the archive store, this node's
        # continuous WAL-segment archiver, and the in-flight backup
        # coordinator op (None unless THIS node is driving one) —
        # built in open() when [backup] names an archive.
        from ..utils.config import BackupConfig
        self.backup_config = backup_config or BackupConfig()
        self.backup_store = None
        self.wal_archiver = None
        self.backup_op = None
        self._backup_mu = threading.Lock()
        self._last_backup: Optional[dict] = None
        self.executor: Optional[Executor] = None
        self.handler: Optional[Handler] = None
        self.pod = None  # parallel.pod.Pod once open() joins a pod

        # Elastic resize (cluster.resize; docs/CLUSTER_RESIZE.md):
        # this node's in-flight coordinator op (None unless THIS node
        # is driving a resize), the post-finalize write-accept grace,
        # and the last settled resize for gossip catch-up.
        self.resize_pace_s = resize_pace_s
        self.resize_grace_s = resize_grace_s
        self.resize_op = None
        self._resize_mu = threading.Lock()
        self._last_resize: Optional[dict] = None

        self._httpd = None
        self._threads: list[threading.Thread] = []
        self._closing = threading.Event()
        # One pooled Client per target host, shared by the executor
        # fan-out and the max-slice poll loop so keep-alive connections
        # actually get reused (client.py pools per Client instance).
        self._clients: dict[str, Client] = {}
        self._clients_mu = threading.Lock()

    def _broadcast_kill(self, qid: str) -> None:
        """Fan a cost-policy kill cluster-wide (sched.tenants): the
        SAME CancelQueryMessage an operator DELETE rides, so peers
        cancel the legs registered under the killed id."""
        from ..cluster.broadcast import CancelQueryMessage
        self.broadcaster.send_async(CancelQueryMessage(qid))

    def client_for(self, host: str) -> Client:
        """The shared keep-alive Client for a peer host."""
        with self._clients_mu:
            client = self._clients.get(host)
            if client is None:
                client = self._clients[host] = Client(
                    host, fault=self.fault, gens=self.gens)
            return client

    def _client_factory(self, host: str) -> Client:
        """client_factory seam for layers that build their own Client
        (anti-entropy, frame restore): fault-aware like client_for,
        but a fresh instance per call (the syncer closes its own)."""
        return Client(host, fault=self.fault, gens=self.gens)

    # -- lifecycle (server.go:89-180) ----------------------------------------

    def open(self) -> None:
        # GIL fairness for multi-tenant latency isolation: CPython's
        # default 5 ms switch interval lets one tenant's CPU-bound
        # handler thread hold the interpreter for whole milliseconds
        # while a quiet tenant's 2 ms query waits — a direct p99
        # transfer between tenants that admission cannot see. 1 ms
        # keeps cross-thread handoff latency ~interference-sized;
        # PILOSA_TPU_GIL_SWITCH_MS overrides (0 keeps the interpreter
        # default). Process-global by nature, set once at open.
        raw_switch = os.environ.get("PILOSA_TPU_GIL_SWITCH_MS", "1")
        try:
            switch_ms = float(raw_switch)
        except ValueError:
            switch_ms = 1.0
        if switch_ms > 0:
            import sys as sys_mod
            sys_mod.setswitchinterval(switch_ms / 1e3)

        bind_host, sep, port_s = self.host.rpartition(":")
        if not sep:  # bare hostname, no port
            bind_host, port_s = self.host, ""
        bind_host = bind_host or "localhost"
        try:
            port = int(port_s) if port_s else 10101
        except ValueError:
            raise PilosaError(f"invalid host: {self.host!r}"
                              " (expected host:port)")

        # Pod membership (multi-host TPU) joins before any jax use so the
        # executor's mesh spans every chip in the pod; a no-op unless the
        # PILOSA_TPU_DIST_* env contract is set (parallel.multihost).
        from ..parallel import multihost, pod as pod_mod
        multihost.initialize_from_env()

        # Persistent XLA compile cache (mesh.arm_compile_cache owns the
        # one rule for where it lives), armed before any device use.
        from ..parallel import mesh as mesh_mod
        mesh_mod.arm_compile_cache()

        self.holder.open()
        # Placement-epoch durability (cluster.resize): a node that
        # lived through resizes must not boot back at epoch 0 with
        # the configured (stale) membership — restore the last
        # persisted (epoch, hosts) pair before anything consults
        # placement.
        self._load_epoch()

        # Pod-internal query broadcast (parallel.pod): the coordinator
        # fans device-batched Count/TopN to every pod process as one
        # collective and replicates schema mutations to pod workers.
        self.pod = pod_mod.maybe_pod(self.holder)
        if self.pod is not None and self.pod.is_coordinator:
            self.broadcaster = pod_mod.PodBroadcaster(self.broadcaster,
                                                      self.pod)

        # Failpoints (fault.failpoints): arm the [fault.failpoints] /
        # PILOSA_FAULT_* schedule before any serving path runs, seeding
        # first so the logged seed reproduces the whole schedule.
        if self.fault_config.seed:
            fault_failpoints.seed_default(self.fault_config.seed)
        for site, spec in (self.fault_config.failpoints or {}).items():
            fault_failpoints.arm(site, spec)
            self.logger.printf("failpoint armed: %s = %s (seed %d)",
                               site, spec,
                               fault_failpoints.default().seed)

        client = _RoutingClient(self)
        self.executor = Executor(
            self.holder, host=self.host, cluster=self.cluster,
            client=client, pod=self.pod, fault=self.fault,
            gens=self.gens, gen_staleness_s=self.gens.staleness_s,
            result_cache_entries=self.query_config.result_cache_entries,
            result_cache_bits=self.query_config.result_cache_bits,
            cluster_cache_entries=self.query_config
            .cluster_cache_entries,
            tenants=self.tenants)
        # Cold-start warmup: background-compile the hot XLA programs so
        # the first real device query doesn't pay the multi-second
        # trace+compile (state surfaces at /status; PILOSA_TPU_WARMUP=0
        # or a disabled mesh skips it).
        if warmup_enabled() and self.executor.use_mesh:
            self.warmup = Warmup(self.executor, logger=self.logger)
            self.warmup.start()
        # On-disk metric history (obs.history): one sampling pass per
        # runtime-collector tick into bounded multi-resolution rings
        # persisted under the data dir (crash-safe; survives SIGKILL
        # minus the unflushed tail).
        if self.metrics_config.enabled and self.history_config.enabled:
            self.history = MetricHistory(
                os.path.join(self.holder.path, "history"),
                resolutions=parse_resolutions(
                    self.history_config.resolutions),
                max_series=self.history_config.max_series,
                segment_bytes=self.history_config.segment_bytes,
                max_segments=self.history_config.segments)
        if self.metrics_config.enabled:
            self.runtime = RuntimeCollector(
                holder=self.holder, executor=self.executor,
                admission=self.admission,
                interval_s=self.metrics_config.runtime_interval,
                slo=self.slo, tenant_slo=self.tenant_slo,
                profiler=self.profiler,
                history=self.history)
        # Cluster federation (obs.federate): /metrics/cluster,
        # /debug/cluster, and history scope=cluster fan a bounded
        # parallel scrape over the pooled (breaker-aware) clients.
        self.federator = Federator(
            self.host, cluster=self.cluster,
            client_for=self.client_for,
            peer_timeout_s=self.metrics_config.federate_timeout,
            fanout=self.metrics_config.federate_fanout)
        # Publish build identity now that jax is loaded (the
        # pilosa_build_info gauge + the /status build block).
        build_info()
        # Tail sampling (obs.sampler): always-on span buffers with an
        # end-of-query keep decision; kept traces persist to a segment
        # ring under the data dir that survives restarts.
        if self.trace_config.tail:
            from ..obs.diskring import SegmentRing
            self.sampler = TailSampler(
                disk=SegmentRing(
                    os.path.join(self.holder.path, "traces"),
                    segment_bytes=self.trace_config.disk_segment_bytes,
                    max_segments=self.trace_config.disk_segments),
                head_n=self.trace_config.head_n,
                slow_floor_s=self.trace_config.slow_floor,
                admission=self.admission)
        # Blackbox flight recorder (obs.blackbox): periodic whole-
        # system snapshots into a bounded disk ring; dumped in full on
        # SIGTERM, fatal thread death, watchdog trip, or the API.
        if self.blackbox_config.enabled:
            self.blackbox = obs_blackbox.Blackbox(
                os.path.join(self.holder.path, "blackbox"),
                state_fn=self._blackbox_state,
                interval_s=self.blackbox_config.interval,
                segment_bytes=self.blackbox_config.segment_bytes,
                max_segments=self.blackbox_config.segments,
                max_dumps=self.blackbox_config.dumps,
                node=self.host, logger=self.logger)
            self.blackbox.start()
            obs_blackbox.install_process_hooks()
        # Storage scrubber + repairer (storage.scrub / server.repair):
        # the scrubber re-verifies on-disk checksums on a paced
        # cadence; the repairer drains the quarantine registry by
        # re-streaming from replicas (woken by the registry's
        # on_quarantine hook, wired in its constructor). Started at
        # the end of open() with the other loops.
        if self.scrub_config.enabled:
            self.scrubber = Scrubber(
                self.holder, interval_s=self.scrub_config.interval,
                pace_s=self.scrub_config.pace, logger=self.logger)
        if self.scrub_config.repair:
            self.repairer = Repairer(
                self.holder, self.cluster, self.host,
                client_factory=self._client_factory, fault=self.fault,
                rescan_s=self.scrub_config.repair_rescan,
                logger=self.logger)
        # Tiered storage (pilosa_tpu.tier; docs/STORAGE.md): the
        # working-set manager — demotion/eviction/blob loops over the
        # residency ledger, honoring per-tenant cache shares, with the
        # prefetcher ranking cold fragments by the metric history's
        # touch rates. Started at the end of open() with the other
        # loops.
        if self.tier_config.enabled:
            self.tier = TierManager(
                self.holder,
                resident_budget=self.tier_config.resident_budget,
                high_watermark=self.tier_config.high_watermark,
                low_watermark=self.tier_config.low_watermark,
                idle_s=self.tier_config.idle,
                blob_idle_s=self.tier_config.blob_idle,
                cold_dir=(self.tier_config.cold_dir
                          or os.path.join(self.holder.path, "_tier")),
                blob=self.tier_config.blob,
                interval_s=self.tier_config.interval,
                prefetch_interval_s=self.tier_config
                .prefetch_interval,
                pace_s=self.tier_config.pace,
                tenants=self.tenants, history=self.history,
                busy_fn=lambda: self.admission.in_flight() > 0,
                logger=self.logger)
            self.holder.tier = self.tier
            # Fragments already opened above get their manager hook
            # now (later opens are picked up by the sync pass).
            self.tier.sync()
        # Disaster recovery (pilosa_tpu.backup;
        # docs/DISASTER_RECOVERY.md): the archive store + this node's
        # continuous WAL-segment archiver. The archiver's sink hooks
        # the group-commit WAL before any traffic arrives, so the
        # PITR record starts at boot, not at the first backup.
        if self.backup_config.archive:
            from ..backup import archive as backup_archive
            from ..backup.walarchive import WalArchiver
            self.backup_store = backup_archive.open_archive(
                self.backup_config.archive, self.holder.path)
            self.wal_archiver = WalArchiver(
                self.backup_store, self.holder.path, self.host,
                interval_s=self.backup_config.wal_interval,
                logger=self.logger)
            self.wal_archiver.start()
        # Stall watchdog (obs.watchdog): wedged WAL flusher, stuck
        # legs, gossip silence, non-draining admission queue. A trip
        # force-keeps in-flight traces and dumps the blackbox.
        if self.watchdog_config.enabled:
            self.watchdog = Watchdog(
                registry=self.query_registry, admission=self.admission,
                tracer=self.tracer, sampler=self.sampler,
                blackbox=self.blackbox,
                gossip_age_fn=self._gossip_age,
                resize_progress_fn=self._resize_progress,
                backup_progress_fn=self._backup_progress,
                scrub_progress_fn=(self.scrubber.stall_age
                                   if self.scrubber is not None
                                   else None),
                tier_progress_fn=(self.tier.stall_age
                                  if self.tier is not None
                                  else None),
                interval_s=self.watchdog_config.interval,
                wal_stall_s=self.watchdog_config.wal_stall,
                deadline_grace_s=self.watchdog_config.deadline_grace,
                gossip_silence_s=self.watchdog_config.gossip_silence,
                queue_stall_s=self.watchdog_config.queue_stall,
                resize_stall_s=self.watchdog_config.resize_stall,
                scrub_stall_s=self.watchdog_config.scrub_stall,
                tier_stall_s=self.watchdog_config.tier_stall,
                backup_stall_s=self.watchdog_config.backup_stall,
                retrip_s=self.watchdog_config.retrip,
                logger=self.logger)
            self.watchdog.start()
        # Regression sentinel (obs.sentinel): slow-cadence robust-z +
        # manifest-envelope rules over the live history; a finding
        # force-keeps in-flight traces (reason ``anomaly``) and lands
        # a blackbox snapshot naming the regressed metric.
        if self.sentinel_config.enabled and self.history is not None:
            self.sentinel = Sentinel(
                self.history, registry=self.query_registry,
                tracer=self.tracer, sampler=self.sampler,
                blackbox=self.blackbox,
                interval_s=self.sentinel_config.interval,
                window_s=self.sentinel_config.window,
                baseline_s=self.sentinel_config.baseline,
                zscore=self.sentinel_config.zscore,
                min_points=self.sentinel_config.min_points,
                min_ratio=self.sentinel_config.min_ratio,
                retrip_s=self.sentinel_config.retrip,
                manifest_path=self.sentinel_config.manifest,
                manifest_tolerance=self.sentinel_config
                .manifest_tolerance,
                logger=self.logger)
            self.sentinel.start()
        # Workload capture (obs.capture): the replayable traffic
        # record behind /debug/capture* — mode "off" still builds the
        # store (a live SIGHUP/env flip can arm it later via config
        # reload patterns) but the handler's enabled check makes the
        # per-request cost one attribute read.
        from ..obs.capture import CaptureStore
        self.capture = CaptureStore(
            os.path.join(self.holder.path, "capture"),
            mode=self.capture_config.mode,
            sample_n=self.capture_config.sample_n,
            segment_bytes=self.capture_config.segment_bytes,
            max_segments=self.capture_config.segments,
            redact_tenants={t.strip() for t in
                            self.capture_config.redact.split(",")
                            if t.strip()},
            node=self.host)
        self.handler = Handler(
            self.holder, self.executor, cluster=self.cluster,
            host=self.host, broadcaster=self.broadcaster,
            broadcast_handler=self, status_handler=self,
            stats=self.stats, client_factory=self._client_factory,
            pod=self.pod,
            logger=self.logger, admission=self.admission,
            registry=self.query_registry, warmup=self.warmup,
            default_timeout_s=self.query_config.default_timeout,
            tracer=self.tracer, runtime=self.runtime,
            profiler=self.profiler,
            accounting=self.metrics_config.accounting,
            fault=self.fault, sampler=self.sampler,
            blackbox=self.blackbox, watchdog=self.watchdog,
            history=self.history, sentinel=self.sentinel,
            federator=self.federator, tenants=self.tenants,
            tenant_slo=self.tenant_slo, scrubber=self.scrubber,
            repairer=self.repairer, tier=self.tier,
            capture=self.capture)

        self._httpd = HTTPServer(self.handler, bind_host, port,
                                 logger=self.logger,
                                 query_batcher=self._query_batcher)
        # Re-resolve the port for ":0" binds (server.go:98-106).
        actual_port = self._httpd.server_address[1]
        if actual_port != port:
            new_host = f"{bind_host}:{actual_port}"
            for n in self.cluster.nodes:
                if n.host == self.host:
                    n.host = new_host
            # The membership backend's identity is the HTTP host; keep it
            # in step so gossip members map back to reachable hosts.
            ns = self.cluster.node_set
            if ns is not None and getattr(ns, "host", None) == self.host:
                ns.host = new_host
            self.host = new_host
            self.executor.host = new_host
            self.handler.host = new_host
            if self.repairer is not None:
                # The repairer's self-identity gates its local-target
                # adapter and peer selection.
                self.repairer.host = new_host
            if self.federator is not None:
                self.federator.host = new_host
            if self.capture is not None:
                # Capture records name the serving node; merged
                # multi-node exports disambiguate on it.
                self.capture.node = new_host
            if self.wal_archiver is not None:
                # WAL segments are keyed by the serving identity;
                # the seq counter is lazy, so no segment has been
                # written under the provisional name yet.
                self.wal_archiver.node = new_host
            if self.fault is not None:
                # The self-identity every fault consult skips.
                self.fault.node = new_host
                self.fault.health.node = new_host
                self.fault.breakers.node = new_host

        # Receiver first, then membership open — the gossip join's
        # push/pull needs the status handler attached (server.go:118,123).
        if self.broadcast_receiver is not None:
            self.broadcast_receiver.start(self)
        if self.cluster.node_set is not None:
            ns = self.cluster.node_set
            if self.fault is not None and hasattr(ns,
                                                  "on_state_change"):
                # Gossip liveness feeds the fault layer: a dead rumor
                # opens the peer's breaker before any query pays a
                # timeout; an alive refutation re-arms the probe.
                ns.on_state_change = self._on_peer_state
            ns.open()

        self.logger.printf("listening as http://%s", self.host)
        # Resize journal recovery: an in-flight resize whose
        # coordinator (us) crashed either aborts back to the old
        # epoch (pre-flip) or rolls forward (post-flip). Runs on a
        # background thread with the cluster up — peers must be
        # reachable for the control sends, and boot must not block
        # on them.
        _rj = resize_mod.ResizeJournal.for_data_dir(self.holder.path)
        if _rj.load() and _rj.in_flight():
            self._spawn(self._recover_resize, "resize-recover")
        # Backup journal recovery: an in-flight backup whose
        # coordinator (us) was killed resumes under the same id —
        # journaled fragments and pool-resident objects are skipped,
        # so recovery converges instead of re-shipping.
        if self.backup_store is not None:
            from ..backup import coordinator as backup_coord
            _bj = backup_coord.BackupJournal.for_data_dir(
                self.holder.path)
            if _bj.load() and _bj.in_flight():
                self._spawn(self._recover_backup, "backup-recover")
        if self.runtime is not None:
            self.runtime.start()
        if self.profile_config.continuous:
            self.profiler.start()
        self._spawn(self._serve, "http")
        self._spawn(self._monitor_cache_flush, "cache-flush")
        if self.polling_interval > 0:
            self._spawn(self._monitor_max_slices, "max-slices")
        if self.anti_entropy_interval > 0:
            self._spawn(self._monitor_anti_entropy, "anti-entropy")
        if self.fault is not None:
            self._spawn(self._monitor_breaker_probes, "fault-probe")
        if self.scrubber is not None:
            self.scrubber.start()
        if self.repairer is not None:
            self.repairer.start()
        if self.tier is not None:
            self.tier.start()
        # Device-queue fairness below admission: dispatch slots stride
        # over the same penalty-boxed tenant weights admission uses
        # (parallel.mesh.FairDispatchQueue; PILOSA_MESH_FAIR=0 vetoes).
        mesh_mod.install_fair_dispatch(self.tenants.effective_weight)

    def close(self) -> None:
        self.logger.printf("server closing: %s", self.host)
        self._closing.set()
        if self.resize_op is not None:
            # Cooperative stop; an in-flight journal is recovered (or
            # aborted) on the next open.
            self.resize_op.cancel()
        if self.backup_op is not None:
            # Cooperative stop; the journal stays in flight so the
            # next open resumes the backup under the same id.
            self.backup_op.cancel()
        if self.wal_archiver is not None:
            # Before the holder closes: the final flush ships every
            # buffered batch, so an orderly shutdown loses no PITR
            # coverage.
            self.wal_archiver.close()
        if self.sentinel is not None:
            self.sentinel.stop()
        # Scrub/repair before the holder closes: a mid-pass verify or
        # re-stream must not race fragment close (both threads join).
        if self.repairer is not None:
            self.repairer.stop()
        if self.scrubber is not None:
            self.scrubber.stop()
        # Tier manager before the holder closes: a mid-pass demotion
        # must not race fragment close (stop() joins the loops).
        if self.tier is not None:
            self.tier.stop()
        from ..parallel import mesh as mesh_mod
        mesh_mod.uninstall_fair_dispatch()
        if self.watchdog is not None:
            self.watchdog.stop()
        if self.blackbox is not None:
            self.blackbox.stop()
        if self.sampler is not None and self.sampler.disk is not None:
            self.sampler.disk.close()
        if self.capture is not None:
            self.capture.close()
        # Collector before history: a mid-tick sample() racing the
        # close would reopen a fresh disk segment after it (stop()
        # joins the collector thread).
        if self.runtime is not None:
            self.runtime.stop()
        if self.history is not None:
            self.history.close()
        self.profiler.stop()
        if self.warmup is not None:
            self.warmup.stop()
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
        if self.cluster.node_set is not None:
            self.cluster.node_set.close()
        if self.executor is not None:
            self.executor.close()
        with self._clients_mu:
            for client in self._clients.values():
                client.close()
            self._clients.clear()
        self.holder.close()

    def _spawn(self, fn, name: str) -> None:
        t = threading.Thread(target=fn, name=f"pilosa-{name}", daemon=True)
        t.start()
        self._threads.append(t)

    def _serve(self) -> None:
        self._httpd.serve_forever()

    def _query_batcher(self, index: str, bodies: list[str], clock):
        """Combine pipelined plain-PQL query bodies into one executor
        call (the httpd batch lane); None falls back to per-request
        dispatch. Partial-failure semantics are IDENTICAL to sequential
        dispatch: execute_partial reports how far the combined call
        stream got — requests fully covered get their results, the
        request holding the failing call gets the error response, and
        requests after it re-execute individually (none of their calls
        ran). Never re-executes an applied mutation (a re-run SetBit
        would report changed=false to the client that set the bit).

        Lifecycle: the combined run occupies ONE admission slot (its
        lane classified over all calls) and registers ONE QueryContext;
        if admission is full the lane declines (None) and the requests
        fall back to per-request dispatch through the handler, which
        produces the proper per-request 429 + Retry-After.

        ``clock`` is the front end's stage clock for the whole batch
        (sched.context): ONE set of stages, folded into the process
        totals with ``requests`` = the batch's size."""
        from ..errors import PilosaError
        from ..executor import _WRITE_CALLS, ExecOptions
        from ..pql import parser as pql
        from ..pql.ast import Query
        from ..sched import (LANE_READ, LANE_WRITE, AdmissionFullError,
                             QueryContext)
        from . import codec
        if self.executor is None:
            return None
        clock.switch("parse")
        try:
            queries = [pql.parse(b) for b in bodies]
        except PilosaError:
            return None
        clock.switch("setup")
        calls = [c for q in queries for c in q.calls]
        if not calls or all(c.name == "SetRowAttrs" for c in calls):
            return None  # bulk-attrs path applies non-positionally
        lane = (LANE_WRITE if any(c.name in _WRITE_CALLS for c in calls)
                else LANE_READ)
        if lane == LANE_WRITE:
            from ..fault import diskfull as fault_diskfull
            if not fault_diskfull.write_ready():
                # Write-unready after ENOSPC: decline the batch so
                # per-request dispatch answers the proper 507s.
                return None
        clock.push("admission")
        try:
            # The batch's tenant is resolved BEFORE the slot is taken
            # (all requests in a batchable run share one index, which
            # IS the principal) — the combined run schedules and
            # charges under it like any single query would.
            slot = self.admission.acquire(lane, tenant=index)
        except AdmissionFullError:
            return None  # per-request dispatch answers the 429s/507s
        finally:
            clock.pop()
        ctx = QueryContext(pql=f"<pipelined batch: {len(calls)} calls>",
                           index=index, lane=lane,
                           timeout_s=self.query_config.default_timeout
                           or None, node=self.host, tenant=index,
                           clock=clock)
        clock.requests = len(bodies)
        if self.metrics_config.accounting:
            obs_accounting.attach(ctx, node=self.host)
        self.tenants.install(ctx)
        err = None  # stays None if execute_partial itself raises —
        # the finally below must never NameError over the real failure
        try:
            with self.query_registry.track(ctx), ctx.stage("execute"):
                results, err = self.executor.execute_partial(
                    index, Query(calls), opt=ExecOptions(ctx=ctx))
            if lane == LANE_WRITE:
                # Commit barrier before the batch's acks go out — ONE
                # leader flush covers every mutation the whole
                # pipelined group applied (storage.wal group commit).
                from ..storage import wal as storage_wal
                with ctx.stage("commit"):
                    storage_wal.barrier_all()
        finally:
            clock.switch("finish")
            slot.release()
            # The batch lane bypasses the handler's query path, so it
            # records its own latency sample (obs.metrics).
            import sys as sys_mod

            from ..obs import metrics as obs_metrics
            failed = err is not None or sys_mod.exc_info()[1] is not None
            labels = ("batch", lane, "500" if failed else "200")
            obs_metrics.QUERY_SECONDS.labels(*labels).observe(
                ctx.elapsed())
            obs_metrics.QUERIES_TOTAL.labels(*labels).inc()

        def ok_payload(rs):
            # Write-heavy pipelined streams answer [true]/[false] for
            # almost every request; skip the per-request JSON encode.
            if len(rs) == 1 and rs[0] is True:
                return b'{"results": [true]}\n'
            if len(rs) == 1 and rs[0] is False:
                return b'{"results": [false]}\n'
            payload = codec.query_response_json(rs, [])
            return (json.dumps(payload) + "\n").encode()

        clock.switch("encode")
        if err is None:
            out = []
            pos = 0
            for q in queries:
                n = len(q.calls)
                out.append(ok_payload(results[pos:pos + n]))
                pos += n
            return out
        out = []
        pos = 0
        failed = False
        for q in queries:
            n = len(q.calls)
            if not failed and len(results) >= pos + n:
                out.append(ok_payload(results[pos:pos + n]))
            elif not failed:
                # This request holds the failing call: the same error
                # response sequential dispatch would produce.
                from ..errors import (QueryCancelledError,
                                      QueryDeadlineError)
                if isinstance(err, QueryDeadlineError):
                    status = 504
                elif isinstance(err, QueryCancelledError):
                    status = 409
                elif isinstance(err, PilosaError):
                    status = 400
                else:
                    status = 500
                body = (json.dumps({"error": str(err)}) + "\n").encode()
                out.append(self._error_payload(body, status))
                failed = True
            else:
                # After the error: none of these calls ran — execute
                # the request normally (per-request error semantics).
                out.append(self._single_query_payload(index, q))
            pos += n
        return out

    def _single_query_payload(self, index: str, q) -> bytes:
        from ..errors import PilosaError
        from . import codec
        try:
            rs = self.executor.execute(index, q)
        except PilosaError as e:
            return self._error_payload(
                (json.dumps({"error": str(e)}) + "\n").encode(), 400)
        except Exception as e:  # noqa: BLE001 - surfaced as 500
            return self._error_payload(
                (json.dumps({"error": str(e)}) + "\n").encode(), 500)
        payload = codec.query_response_json(rs, [])
        return (json.dumps(payload) + "\n").encode()

    @staticmethod
    def _error_payload(body: bytes, status: int) -> bytes:
        """A non-200 batch-lane entry: the httpd renders 200 for plain
        bytes, so error entries carry their own status marker."""
        return (status, body)

    def _on_peer_state(self, host: str, state: str) -> None:
        """Gossip membership callback → fault layer (cluster.gossip
        states map 1:1 onto health's liveness vocabulary)."""
        if self.fault is not None:
            self.fault.note_gossip(host, state)

    # -- elastic resize (cluster.resize; docs/CLUSTER_RESIZE.md) -------------

    def start_resize(self, target_hosts: list[str]):
        """Begin an online resize to ``target_hosts`` with THIS node
        as coordinator; returns the ResizeCoordinator (already running
        on a background thread). One at a time — cluster-wide, the
        prepare install enforces it; locally, this guard does."""
        with self._resize_mu:
            op = self.resize_op
            # A just-constructed coordinator sits in IDLE until its
            # thread reaches the first phase — IDLE with no finish
            # time IS in flight, or two rapid POSTs would both pass
            # the guard and share one journal (review finding).
            if op is not None and not (
                    op.phase in (resize_mod.PHASE_DONE,
                                 resize_mod.PHASE_ABORTED)
                    or op.finished_at):
                raise PilosaError(
                    f"resize {op.id} already in flight"
                    f" (phase {op.phase})")
            if self.cluster.resize is not None:
                raise PilosaError(
                    f"resize {self.cluster.resize.id} already"
                    f" installed cluster-wide")
            # Journal recovery may still be rolling a prior resize
            # forward on its background thread (it registers itself
            # as resize_op only once it runs) — an in-flight journal
            # refuses new resizes outright so two coordinators can
            # never interleave writes to it. The one settle-able
            # state: an ABORT whose broadcast never reached a (since
            # dead) peer — re-send it now; if every node acks, the
            # old resize is settled and the new one may start.
            _rj = resize_mod.ResizeJournal.for_data_dir(
                self.holder.path)
            if _rj.load() and _rj.in_flight():
                if _rj.state.get("phase") == resize_mod.PHASE_ABORTED:
                    stale = resize_mod.ResizeCoordinator(
                        self, _rj.state.get("new") or [],
                        resize_id=str(_rj.state.get("id")),
                        journal=_rj, logger=self.logger)
                    stale.old_hosts = _rj.state.get("old") or []
                    stale.abort(reason="settling unacked abort before"
                                       " a new resize")
                    _rj.load()
                if _rj.in_flight():
                    raise PilosaError(
                        f"resize {_rj.state.get('id')} still settling"
                        f" (journal phase {_rj.state.get('phase')})")
            coord = resize_mod.ResizeCoordinator(
                self, target_hosts, pace_s=self.resize_pace_s,
                grace_s=self.resize_grace_s, logger=self.logger)
            self.resize_op = coord
        self._spawn(coord.run, f"resize-{coord.id}")
        return coord

    def abort_resize(self) -> Optional[dict]:
        """Operator abort: back the in-flight resize out to the old
        epoch. Works from the coordinator (aborts its op) or any node
        that merely has the state installed (broadcasts abort on the
        coordinator's behalf)."""
        op = self.resize_op
        if op is not None and op.phase not in (
                resize_mod.PHASE_DONE, resize_mod.PHASE_ABORTED):
            op.abort(reason="operator abort")
            return op.status()
        rs = self.cluster.resize
        if rs is None:
            return None
        coord = resize_mod.ResizeCoordinator(
            self, rs.new_hosts, resize_id=rs.id, logger=self.logger)
        coord.old_hosts = list(rs.old_hosts)
        # Seed the journal with the full membership BEFORE the abort
        # lands in it: if the abort broadcast can't reach every node
        # and this node restarts, recovery must be able to re-send it
        # to the right hosts (an id-less abort record re-sends to
        # nobody yet marks itself acked — review finding).
        coord.journal.write(id=rs.id, epochFrom=rs.epoch_from,
                            old=list(rs.old_hosts),
                            new=list(rs.new_hosts),
                            coordinator=self.host)
        coord.abort(reason="operator abort (non-coordinator)")
        return coord.status()

    def _recover_resize(self) -> None:
        try:
            status = resize_mod.recover(self, logger=self.logger)
            if status is not None:
                self.logger.printf("resize recovery finished: %s",
                                   status.get("phase"))
        except Exception as e:  # noqa: BLE001 - recovery best-effort
            self.logger.printf("resize recovery failed: %s", e)

    def _resize_progress(self):
        """Watchdog hook (obs.watchdog cause ``resize_stall``):
        (phase, seconds-without-progress) while this node coordinates
        an active resize, else None."""
        op = self.resize_op
        if op is None:
            return None
        if op.phase in (resize_mod.PHASE_IDLE, resize_mod.PHASE_DONE,
                        resize_mod.PHASE_ABORTED):
            return None
        import time as time_mod
        return op.phase, time_mod.monotonic() - op.last_progress

    # -- cluster backup (pilosa_tpu.backup; docs/DISASTER_RECOVERY.md) --------

    def start_backup(self, kind: str = "full"):
        """Begin a cluster backup into the configured archive with
        THIS node as coordinator; returns the BackupCoordinator
        (already running on a background thread). One at a time per
        node — the journal is single-writer."""
        from ..backup import coordinator as backup_coord
        if self.backup_store is None:
            raise PilosaError("no backup archive configured"
                              " ([backup] archive)")
        with self._backup_mu:
            op = self.backup_op
            if op is not None and not (
                    op.phase in (backup_coord.PHASE_DONE,
                                 backup_coord.PHASE_FAILED)
                    or op.finished_at):
                raise PilosaError(
                    f"backup {op.id} already in flight"
                    f" (phase {op.phase})")
            # An in-flight journal belongs to a backup still being
            # recovered (recovery registers itself as backup_op only
            # once it runs) — refuse rather than interleave two
            # coordinators into one journal.
            _bj = backup_coord.BackupJournal.for_data_dir(
                self.holder.path)
            if _bj.load() and _bj.in_flight() and (
                    op is None or _bj.state.get("id") != op.id):
                raise PilosaError(
                    f"backup {_bj.state.get('id')} still recovering"
                    f" (journal phase {_bj.state.get('phase')})")
            coord = backup_coord.BackupCoordinator(
                self, self.backup_store, kind=kind,
                logger=self.logger)
            self.backup_op = coord
        self._spawn(coord.run, f"backup-{coord.id}")
        return coord

    def abort_backup(self) -> Optional[dict]:
        """Operator abort: cooperatively stop the in-flight backup
        this node coordinates. The journal stays in flight, so the
        next open (or a later POST) resumes it instead of discarding
        the objects already pushed."""
        from ..backup import coordinator as backup_coord
        op = self.backup_op
        if op is None or op.phase in (backup_coord.PHASE_DONE,
                                      backup_coord.PHASE_FAILED) \
                or op.finished_at:
            return None
        op.cancel()
        return op.status()

    def _recover_backup(self) -> None:
        try:
            from ..backup import coordinator as backup_coord
            status = backup_coord.recover(self, logger=self.logger)
            if status is not None:
                self.logger.printf("backup recovery finished: %s",
                                   status.get("phase"))
        except Exception as e:  # noqa: BLE001 - recovery best-effort
            self.logger.printf("backup recovery failed: %s", e)

    def _backup_progress(self):
        """Watchdog hook (obs.watchdog cause ``backup_stall``):
        seconds-without-progress while this node coordinates an active
        backup, else None."""
        from ..backup import coordinator as backup_coord
        op = self.backup_op
        if op is None or op.phase in (backup_coord.PHASE_IDLE,
                                      backup_coord.PHASE_DONE,
                                      backup_coord.PHASE_FAILED):
            return None
        import time as time_mod
        return time_mod.monotonic() - op.last_progress

    def _epoch_path(self) -> str:
        return os.path.join(self.holder.path, "epoch.json")

    def _save_epoch(self) -> None:
        """Persist (epoch, membership) on every epoch transition —
        without it a restarted node resets to epoch 0 with its
        boot-config membership, silently mis-placing every slice and
        (post-fix) refusing every future resize's prepare (review
        finding)."""
        try:
            tmp = self._epoch_path() + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"epoch": self.cluster.epoch,
                           "hosts": [n.host
                                     for n in self.cluster.nodes]}, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self._epoch_path())
        except OSError as e:
            self.logger.printf("epoch persist failed: %s", e)

    def _load_epoch(self) -> None:
        try:
            with open(self._epoch_path()) as f:
                d = json.load(f)
            epoch = int(d.get("epoch", 0))
            hosts = [str(h) for h in (d.get("hosts") or [])]
        except (OSError, ValueError, TypeError):
            return
        if epoch <= self.cluster.epoch or not hosts:
            return
        if self.host.endswith(":0"):
            # A ":0" bind re-resolves its port after this point, so
            # the persisted membership names a port this node no
            # longer answers on and cannot be stitched back — skip
            # adoption (ephemeral-port servers are test harness
            # territory; production binds are stable).
            return
        self.cluster.nodes = [Node(h) for h in hosts]
        self.cluster.epoch = epoch
        self.logger.printf(
            "restored placement epoch %d (%d members) from %s",
            epoch, len(hosts), self._epoch_path())

    def _moved_fn(self, moving: dict):
        """``moved(index, slice) -> bool`` over a captured moving-
        partition map — the executor's eager cache flush on a flip."""
        parts = frozenset(moving)
        partition = self.cluster.partition

        def moved(index: str, slice: int) -> bool:
            return partition(index, slice) in parts
        return moved

    def _apply_resize_message(self, m: ResizeMessage) -> None:
        """One node's side of the resize protocol. Every phase is
        idempotent — the coordinator retries control sends, and the
        gossip catch-up path replays them — and a node that missed
        earlier phases reconstructs them from the message itself (it
        carries the full old/new membership)."""
        cl = self.cluster
        ex = self.executor

        def _install() -> bool:
            if cl.resize is not None:
                if cl.resize.id != m.id:
                    raise PilosaError(
                        f"resize {cl.resize.id} already in flight;"
                        f" refusing {m.id}")
                return True
            last = self._last_resize
            if last is not None and last.get("id") == m.id:
                # This id already settled here (aborted or done): a
                # straggling control send racing the abort broadcast
                # — or a gossip replay — must never re-install it.
                return False
            if cl.epoch > m.epoch:
                # AHEAD of the message: a resize minted against a
                # past epoch (e.g. a coordinator that restarted
                # before converging). A silent 200 would fake the
                # all-ack while this node never installs — refuse
                # loudly; legitimate replays of SETTLED resizes were
                # already absorbed by the _last_resize guard above.
                raise PilosaError(
                    f"resize {m.id} minted at epoch {m.epoch} but"
                    f" this node is at {cl.epoch}")
            if cl.epoch < m.epoch:
                # This node is BEHIND (restarted at epoch 0 / missed
                # flips). A silent 200 here would count as the all-ack
                # the union-write guarantee rests on while the node
                # keeps routing writes old-placement-only — the
                # coordinator must see a FAILURE and retry until the
                # gossip catch-up brings the node forward (or abort).
                raise PilosaError(
                    f"node at placement epoch {cl.epoch}, resize"
                    f" {m.id} expects {m.epoch} — catching up")
            cl.install_resize(m.id, m.new_hosts)
            resize_mod.set_state_gauge("migrating")
            if ex is not None:
                ex.on_resize_change()
            self.logger.printf(
                "resize %s: installed (epoch %d, %s -> %s)", m.id,
                m.epoch, m.old_hosts, m.new_hosts)
            return True

        if m.phase == "prepare":
            _install()
            return
        if m.phase == "flip":
            if cl.epoch == m.epoch + 1:
                return  # already flipped (retry / catch-up replay)
            if not _install():
                return
            rs = cl.resize
            if rs is None or rs.id != m.id:
                return  # aborted concurrently on another thread
            moving = dict(rs.moving)
            try:
                flipped = cl.flip_epoch(m.id)
            except ValueError:
                return  # abort raced the flip: settled-id guard holds
            if flipped:
                self._save_epoch()
                resize_mod.set_state_gauge(resize_mod.PHASE_DRAINING)
                if ex is not None:
                    ex.on_resize_change(self._moved_fn(moving))
                self.logger.printf(
                    "resize %s: FLIPPED to epoch %d (%d moving"
                    " partitions)", m.id, cl.epoch, len(moving))
            return
        if m.phase == "finalize":
            if cl.resize is None and cl.epoch == m.epoch:
                # Missed prepare AND flip (restart/partition): replay
                # both from this message, then finalize below.
                if not _install():
                    return
            rs = cl.resize
            if rs is not None and rs.id == m.id:
                moving = dict(rs.moving)
                from ..cluster.topology import RESIZE_DRAINING
                try:
                    if rs.phase != RESIZE_DRAINING:
                        cl.flip_epoch(m.id)
                        if ex is not None:
                            ex.on_resize_change(self._moved_fn(moving))
                    cl.finalize_resize(m.id,
                                       grace_s=self.resize_grace_s)
                except ValueError:
                    return  # abort raced this application
                self._save_epoch()
                resize_mod.set_state_gauge(resize_mod.PHASE_IDLE)
                if ex is not None:
                    ex.on_resize_change()
                self._last_resize = {
                    "id": m.id, "outcome": "done",
                    "epochFrom": m.epoch, "old": m.old_hosts,
                    "new": m.new_hosts}
                self.logger.printf("resize %s: finalized (epoch %d)",
                                   m.id, cl.epoch)
            return
        if m.phase == "abort":
            # A live coordinator op for this id (an abort initiated
            # through ANOTHER node) must stop driving the protocol —
            # its later phases would otherwise be silently absorbed
            # by every node's settled-id guard and the journal would
            # record 'done' for a resize the cluster aborted.
            op = self.resize_op
            if op is not None and op.id == m.id:
                op.cancel()
            rs = cl.resize
            moving = dict(rs.moving) if rs is not None else {}
            aborted = cl.abort_resize(m.id)
            # Record the settled outcome even when nothing was
            # installed here (a node that missed prepare): a
            # straggling prepare/flip for this id must never install
            # it afterwards.
            self._last_resize = {
                "id": m.id, "outcome": "aborted",
                "epochFrom": m.epoch, "old": m.old_hosts,
                "new": m.new_hosts}
            if aborted:
                self._save_epoch()  # covers a post-flip revert
                resize_mod.set_state_gauge(resize_mod.PHASE_IDLE)
                if ex is not None:
                    ex.on_resize_change(self._moved_fn(moving))
                self.logger.printf("resize %s: aborted (epoch stays"
                                   " %d)", m.id, cl.epoch)
            return
        raise PilosaError(f"unknown resize phase: {m.phase!r}")

    # -- gossip piggyback: epoch/resize convergence --------------------------

    def resize_wire_state(self) -> dict:
        """Rides the gossip push/pull full-state exchange so a node
        that missed resize control sends (partitioned, restarted)
        converges on the cluster's placement epoch within one
        anti-entropy period."""
        out: dict = {"epoch": self.cluster.epoch}
        rs = self.cluster.resize
        if rs is not None:
            out["resize"] = rs.to_wire()
        if self._last_resize is not None:
            out["last"] = dict(self._last_resize)
        return out

    def apply_resize_wire_state(self, d: dict) -> None:
        """Converge toward a peer's epoch/resize knowledge. Only ever
        moves FORWARD (install → flip → finalize, or abort of the
        exact in-flight id) — a peer that is itself behind can never
        drag us back."""
        try:
            peer_epoch = int(d.get("epoch", 0))
        except (TypeError, ValueError):
            return
        cl = self.cluster
        rz = d.get("resize")
        last = d.get("last")

        def msg(phase: str, src: dict) -> ResizeMessage:
            return ResizeMessage(
                id=str(src.get("id", "")), phase=phase,
                epoch=int(src.get("epochFrom", peer_epoch - 1)),
                old_hosts=src.get("old") or [],
                new_hosts=src.get("new") or [])
        try:
            if (cl.resize is not None and rz is None and last
                    and last.get("id") == cl.resize.id):
                # The resize WE still carry has settled at the peer.
                if last.get("outcome") == "aborted":
                    self._apply_resize_message(msg("abort", last))
                elif last.get("outcome") == "done":
                    self._apply_resize_message(msg("finalize", last))
                return
            if rz is not None:
                if (rz.get("phase") == "draining"
                        and peer_epoch == cl.epoch + 1):
                    self._apply_resize_message(msg("flip", rz))
                elif (peer_epoch == cl.epoch and cl.resize is None):
                    self._apply_resize_message(msg("prepare", rz))
                return
            if peer_epoch > cl.epoch and last and int(
                    last.get("epochFrom", -1)) == cl.epoch:
                # Peer finalized a resize we never heard of at all.
                self._apply_resize_message(msg("finalize", last))
        except Exception as e:  # noqa: BLE001 - convergence best-effort
            self.logger.printf("resize gossip catch-up skipped: %s", e)

    # -- fleet observability (obs.federate; docs/OBSERVABILITY.md) -----------

    def local_debug_state(self) -> dict:
        """This node's block of the ``/debug/cluster`` rollup: the
        blackbox state, fleet-queryable — build identity, placement
        epoch, breaker states, SLO burn, WAL flusher health, resize
        phase, admission shape. Deliberately lighter than
        ``_blackbox_state`` (no thread dump, no generation map, no
        slow-log bodies): a fleet-wide fan-out must stay cheap on
        every leg."""
        from ..storage import wal as storage_wal
        out: dict = {"host": self.host,
                     "build": build_info(),
                     "epoch": self.cluster.epoch,
                     "admission": self.admission.snapshot(),
                     "wal": storage_wal.flusher_health(),
                     "quarantined": len(self.holder.quarantine)}
        if self.fault is not None:
            out["fault"] = self.fault.snapshot()
        if self.runtime is not None:
            rt = self.runtime.snapshot()
            if rt.get("slo") is not None:
                out["slo"] = rt["slo"]
            if rt.get("holder") is not None:
                out["holder"] = rt["holder"]
            if rt.get("deviceBlockCache"):
                out["deviceBlockCache"] = rt["deviceBlockCache"]
        if self.watchdog is not None:
            out["watchdog"] = self.watchdog.snapshot()
        if self.sentinel is not None:
            out["sentinel"] = self.sentinel.snapshot()
        if self.history is not None:
            out["history"] = self.history.stats()
        rs = self.cluster.resize
        out["resize"] = {"phase": (rs.phase if rs is not None
                                   else "idle"),
                         "inFlight": rs.to_wire()
                         if rs is not None else None}
        if self.resize_op is not None:
            out["resize"]["op"] = self.resize_op.status()
        if self.peer_builds:
            out["gossipBuilds"] = dict(self.peer_builds)
        return out

    # -- gossip piggyback: build identity (version-skew visibility) ----------

    def build_wire_state(self) -> dict:
        """Rides the gossip push/pull next to the resize state: each
        node's build identity, so a mixed-version fleet's skew is
        visible from ANY member during a rolling restart — even for
        peers an HTTP scrape can't currently reach."""
        return {"host": self.host, **build_info()}

    def apply_build_wire_state(self, d: dict) -> None:
        try:
            host = str(d.get("host", ""))
        except (TypeError, ValueError):
            return
        if not host or host == self.host:
            return
        self.peer_builds[host] = {
            k: str(d.get(k, "")) for k in ("version", "python", "jax",
                                           "backend")}

    # -- blackbox / watchdog wiring (obs subsystem) --------------------------

    def _gossip_age(self) -> Optional[float]:
        """Seconds of membership silence for the watchdog, or None
        when not observable (static membership, single node)."""
        ns = self.cluster.node_set if self.cluster is not None else None
        if ns is None or not hasattr(ns, "last_activity_age"):
            return None
        return ns.last_activity_age()

    def _blackbox_state(self) -> dict:
        """One whole-system snapshot for the flight recorder: the
        states an incident retro always wants and can never get after
        the fact — queues, breakers, generation knowledge, the WAL
        dirty set + flusher heartbeat, cache/runtime counters, recent
        slow queries, and a thread dump."""
        from ..storage import wal as storage_wal
        from ..utils.profiling import thread_dump
        out: dict = {"host": self.host,
                     "admission": self.admission.snapshot(),
                     "wal": storage_wal.flusher_health()}
        if self.fault is not None:
            out["fault"] = self.fault.snapshot()
        out["generations"] = self.gens.snapshot()
        reg = self.query_registry
        out["queries"] = {"active": reg.active()[:32],
                          "slow": reg.slow_queries()[-8:]}
        if self.runtime is not None:
            # The collector's last background sample (holder shape,
            # residency, compile-cache, SLO burn) — cheap to reuse.
            out["runtime"] = self.runtime.snapshot()
        if self.executor is not None:
            out["executor"] = {
                "deviceFallbacks": getattr(self.executor,
                                           "device_fallbacks", 0),
                "costModelVetoes": getattr(self.executor,
                                           "cost_vetoes", 0)}
            planner = getattr(self.executor, "planner", None)
            if planner is not None:
                # Decision totals + subresult-cache occupancy: "was
                # the planner rewriting when it went wrong" is a
                # first-hour retro question.
                out["planner"] = planner.snapshot()
        if self.watchdog is not None:
            out["watchdog"] = self.watchdog.snapshot()
        # Elastic resize state: phase, movement progress, epoch — the
        # one thing a mid-migration incident retro always asks first.
        rs = self.cluster.resize
        resize_block: dict = {"epoch": self.cluster.epoch,
                              "inFlight": rs.to_wire()
                              if rs is not None else None}
        if self.resize_op is not None:
            resize_block["op"] = self.resize_op.status()
        out["resize"] = resize_block
        # Storage integrity: quarantined fragments + scrub/repair
        # progress — the retro question after any wrong-answer scare.
        integrity_block: dict = {
            "quarantined": self.holder.quarantine.entries()[:32]}
        if self.scrubber is not None:
            integrity_block["scrub"] = self.scrubber.state()
        if self.repairer is not None:
            integrity_block["repair"] = self.repairer.state()
        out["integrity"] = integrity_block
        # Tiered storage: residency counts, watermarks, blocked cold
        # fetches — where did the working set live when it happened.
        if self.tier is not None:
            out["tier"] = self.tier.state()
        # Disaster recovery: the in-flight backup op + this node's
        # WAL-archiver lag — "was a backup running, and how much PITR
        # coverage was buffered" is the post-crash retro question.
        if self.backup_store is not None:
            backup_block: dict = {"configured": True}
            if self.backup_op is not None:
                backup_block["op"] = self.backup_op.status()
            if self.wal_archiver is not None:
                backup_block["walArchiver"] = self.wal_archiver.state()
            out["backup"] = backup_block
        try:
            out["threads"] = thread_dump()[:20000]
        except Exception:  # noqa: BLE001 - interpreter-internal API
            pass
        return out

    # -- slice announcements (view.go:236-246) -------------------------------

    def _on_create_slice(self, index: str, slice: int,
                         inverse: bool) -> None:
        try:
            self.broadcaster.send_async(pb.CreateSliceMessage(
                Index=index, Slice=slice, IsInverse=inverse))
        except Exception:  # noqa: BLE001 - announcements are best-effort
            pass

    # -- background loops ----------------------------------------------------

    def _loop(self, interval: float, fn, name: str = "loop") -> None:
        while not self._closing.wait(interval):
            try:
                fn()
            except Exception as e:  # noqa: BLE001 - loops must survive errors
                self.logger.printf("%s error: %s", name, e)

    def _monitor_cache_flush(self) -> None:
        self._loop(CACHE_FLUSH_INTERVAL, self.holder.flush_caches,
                   "holder cache flush")

    def _monitor_max_slices(self) -> None:
        # Poll peers' /slices/max and adopt larger values
        # (server.go:216-252).
        self._loop(self.polling_interval, self.poll_max_slices,
                   "max slices poll")

    def poll_max_slices(self) -> None:
        for node in self.cluster.nodes:
            if node.host == self.host:
                continue
            client = self.client_for(node.host)
            for name, value in client.max_slices().items():
                idx = self.holder.index(name)
                if idx is not None:
                    idx.set_remote_max_slice(value)
            for name, value in client.max_slices(inverse=True).items():
                idx = self.holder.index(name)
                if idx is not None:
                    idx.set_remote_max_inverse_slice(value)

    _BREAKER_PROBE_INTERVAL = 1.0

    def _monitor_breaker_probes(self) -> None:
        """Active half-open probing: a peer behind an open circuit gets
        NO traffic (that is the point), so recovery cannot rely on
        query placement happening to route it a request — in many
        topologies it never would. This loop sends each probe-ready
        peer one cheap /version request; the fault-aware client takes
        the half-open probe slot, and the outcome closes or re-opens
        the breaker through the ordinary feed."""
        self._loop(self._BREAKER_PROBE_INTERVAL,
                   self.probe_open_breakers, "breaker probe")

    def probe_open_breakers(self) -> None:
        from ..errors import QueryDeadlineError
        for host in (self.fault.probe_targets()
                     if self.fault is not None else ()):
            try:
                # deadline_s clamps the probe's socket timeout: a
                # blackholed peer must not pin this loop for the
                # client's full 30 s default.
                self.client_for(host)._do("GET", "/version",
                                          deadline_s=2.0)
            except QueryDeadlineError:
                # The client deliberately does NOT feed budget-clamped
                # timeouts to the breaker (tight query deadlines must
                # not condemn healthy peers) — but the probe's 2 s IS
                # the probe's verdict: a peer that can't answer
                # /version in 2 s stays open.
                self.fault.record_rpc(host, False)
            except Exception:  # noqa: BLE001 - outcome fed the breaker
                pass

    def _monitor_anti_entropy(self) -> None:
        from .syncer import HolderSyncer

        def run():
            # server.go:182-214 logs the start and total duration of
            # every anti-entropy sweep.
            with self.logger.track("holder sync"):
                HolderSyncer(self.holder, self.host, self.cluster,
                             closing=self._closing,
                             client_factory=self._client_factory,
                             fault=self.fault,
                             logger=self.logger).sync_holder()

        self._loop(self.anti_entropy_interval, run, "anti-entropy")

    # -- BroadcastHandler (server.go:255-300) --------------------------------

    def receive_message(self, m) -> None:
        if isinstance(m, pb.CreateSliceMessage):
            idx = self.holder.index(m.Index)
            if idx is None:
                return
            if m.IsInverse:
                idx.set_remote_max_inverse_slice(m.Slice)
            else:
                idx.set_remote_max_slice(m.Slice)
        elif isinstance(m, pb.CreateIndexMessage):
            self.holder.create_index_if_not_exists(
                m.Index, IndexOptions.decode(m.Meta))
        elif isinstance(m, pb.DeleteIndexMessage):
            self.holder.delete_index(m.Index)
        elif isinstance(m, pb.CreateFrameMessage):
            idx = self.holder.index(m.Index)
            if idx is not None:
                opts = FrameOptions.decode(m.Meta)
                frame = idx.create_frame_if_not_exists(m.Frame, opts)
                # Field creation on an existing frame re-broadcasts the
                # full meta: register any fields this node lacks
                # (create_field is idempotent on a matching range).
                for fld in opts.fields or []:
                    frame.create_field(fld)
        elif isinstance(m, pb.DeleteFrameMessage):
            idx = self.holder.index(m.Index)
            if idx is not None:
                idx.delete_frame(m.Frame)
        elif isinstance(m, ResizeMessage):
            # Elastic resize control plane (cluster.resize): prepare /
            # flip / finalize / abort, delivered as direct acked POSTs
            # by the coordinator and replayed via gossip for
            # stragglers.
            self._apply_resize_message(m)
        elif isinstance(m, CancelQueryMessage):
            # Cluster-wide cancellation (sched subsystem): kill every
            # leg registered under this id on THIS node — the
            # coordinator's entry query and forwarded remote legs both
            # carry the same id.
            n = self.query_registry.cancel_local(
                m.id, reason="cancelled cluster-wide")
            if n:
                self.logger.printf("cancelled query %s (%d context%s)",
                                   m.id, n, "" if n == 1 else "s")
        else:
            raise ValueError(f"unexpected message: {m!r}")

    # -- StatusHandler (server.go:306-440) -----------------------------------

    def local_status(self) -> pb.NodeStatus:
        """This node's state as the wire type the gossip push/pull
        carries: schema with metas + the slice list this node owns per
        index (server.go:306-323, internal/private.proto NodeStatus)."""
        indexes = []
        for name in sorted(self.holder.indexes):
            idx = self.holder.indexes[name]
            max_slice = idx.max_slice()
            indexes.append(pb.Index(
                Name=name, Meta=idx.options.encode(), MaxSlice=max_slice,
                Frames=[pb.Frame(Name=fn,
                                 Meta=idx.frames[fn].options.encode())
                        for fn in sorted(idx.frames)],
                Slices=self.cluster.owns_slices(name, max_slice,
                                                self.host)))
        return pb.NodeStatus(Host=self.host, State=NODE_STATE_UP,
                             Indexes=indexes)

    def cluster_status(self) -> pb.ClusterStatus:
        """NodeStatus for every node: ours live, peers from the last
        status merge, membership deciding UP/DOWN (server.go:325-351)."""
        states = self.cluster.node_states()
        nodes = []
        for n in self.cluster.nodes:
            if n.host == self.host:
                nodes.append(self.local_status())
                continue
            ns = pb.NodeStatus()
            if n.status is not None:
                ns.CopyFrom(n.status)
            ns.Host = n.host
            ns.State = states.get(n.host, NODE_STATE_DOWN)
            nodes.append(ns)
        return pb.ClusterStatus(Nodes=nodes)

    def handle_remote_status(self, status: pb.NodeStatus) -> None:
        """Merge a peer's schema + owned-slice knowledge into ours
        (server.go:353-387 mergeRemoteStatus)."""
        node = self.cluster.node_by_host(status.Host)
        if node is not None:
            node.set_status(status)
        for idx_info in status.Indexes:
            idx = self.holder.create_index_if_not_exists(
                idx_info.Name, IndexOptions.decode(idx_info.Meta))
            remote_max = max([idx_info.MaxSlice] +
                             [int(s) for s in idx_info.Slices])
            idx.set_remote_max_slice(remote_max)
            for frame_info in idx_info.Frames:
                idx.create_frame_if_not_exists(
                    frame_info.Name, FrameOptions.decode(frame_info.Meta))


class _RoutingClient:
    """Executor transport that routes to whatever node is asked for
    (the executor passes the target node per call). deadline_aware:
    lifecycle kwargs (remaining budget + query id) pass straight
    through to the underlying pooled Client, which clamps socket
    timeouts/retries and stamps the fan-out headers."""

    deadline_aware = True
    generation_aware = True

    def __init__(self, server: Server):
        self.server = server

    def execute_query(self, node, index, query, slices, remote,
                      pod_local=False, deadline_s=None, query_id=None,
                      gens_out=None):
        # gens_out travels only when set — test fixtures fake the
        # pooled client with the pre-generations signature.
        kwargs = {"gens_out": gens_out} if gens_out is not None else {}
        return self.server.client_for(node.host).execute_query(
            node, index, query, slices, remote=remote,
            pod_local=pod_local, deadline_s=deadline_s,
            query_id=query_id, **kwargs)

    def generations(self, index, slices=None, host=None,
                    deadline_s=None):
        """The executor's cluster-cache validation probe, routed
        through the pooled per-host Client."""
        return self.server.client_for(host).generations(
            index, slices, deadline_s=deadline_s)
