"""Observability subsystem: metrics, distributed tracing, and the
runtime collector.

- ``obs.metrics`` — a Prometheus-style registry (labeled counters,
  gauges, log-bucketed histograms) rendered at ``GET /metrics``; every
  metric family the server emits is declared there at import, and a
  ``RegistryStatsClient`` bridge feeds legacy ``StatsClient`` call
  sites into the same registry so no call site changes twice.
- ``obs.trace`` — per-query distributed traces: spans opened at parse,
  admission, executor fan-out, per-leg RPCs, mesh dispatch, and XLA
  compile; remote legs return their spans piggybacked on the internal
  query response and the coordinator stitches them under one trace id
  (the query id riding ``X-Pilosa-Query-Id``). A bounded per-node ring
  serves ``GET /debug/traces`` and Chrome trace-event export.
- ``obs.accounting`` — per-query cost ledgers (EXPLAIN ANALYZE for
  PQL): container ops by operand-kind pair, words scanned, bits
  written, device programs/bytes, compile ms, RPC bytes per peer;
  remote legs piggyback their ledger on ``X-Pilosa-Cost`` and the
  coordinator stitches a per-node cost tree (``?profile=1``,
  ``X-Pilosa-Stats``, /debug/queries, the slow log, span args).
- ``obs.profile`` — the always-on low-Hz continuous wall profiler:
  query-id-tagged folded stacks in a bounded ring, served as
  speedscope-loadable collapsed-stack text at ``/debug/pprof/flame``.
- ``obs.slo`` — rolling latency-objective burn rates over the query
  histograms, OpenMetrics exemplars carrying trace ids, and the
  ``GET /health`` readiness checks.
- ``obs.runtime`` — a background collector sampling holder/cache/
  residency sizes, thread activity, and the XLA compile-cache
  counters (parallel.mesh.compile_stats) into gauges and ``/status``.
- ``obs.sampler`` — always-on tail-sampled tracing: every query gets
  the span buffer, the keep decision runs at query end (slow/errored/
  deadline/cancelled/partial/shed/breaker/failpoint/head), and kept
  traces persist to a crash-safe on-disk segment ring
  (``obs.diskring``) that survives restarts.
- ``obs.blackbox`` — the flight recorder: periodic whole-system
  snapshots into a bounded disk ring, dumped in full on SIGTERM,
  fatal thread death, a watchdog trip, or the API.
- ``obs.watchdog`` — the stall watchdog: wedged WAL flusher, legs
  stuck past deadline grace, gossip silence, non-draining admission
  queue → ``pilosa_watchdog_trips_total{cause}``, force-kept
  in-flight traces, a blackbox dump.
- ``obs.history`` — the on-disk metric history: every registry
  family sampled on the collector cadence into bounded
  multi-resolution rings (counters as rates, histograms as
  p50/p99/rate series) persisted crash-safe under the data dir;
  served at ``GET /debug/metrics/history``.
- ``obs.federate`` — cluster-wide aggregation at query time:
  ``GET /metrics/cluster`` (counters sum, histograms merge, gauges
  per-node) and the ``GET /debug/cluster`` fleet rollup, over a
  bounded breaker-aware parallel scrape with the ``?partial=1``
  degradation contract.
- ``obs.sentinel`` — the regression sentinel: robust-z rules over
  the live history plus envelope rules against the operator's
  ``[sentinel] manifest`` file; a finding raises
  ``pilosa_sentinel_findings_total{metric,direction}``, force-keeps
  in-flight traces (reason ``anomaly``), and lands a blackbox
  snapshot naming the regressed metric.

See docs/OBSERVABILITY.md for the metric name reference, the trace
and cost wire contracts, and the perfetto/speedscope how-tos.
"""

from .metrics import (RegistryStatsClient, Registry,  # noqa: F401
                      default_registry)
from .trace import Tracer, get_tracer  # noqa: F401
