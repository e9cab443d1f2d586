"""Runtime collector: periodic gauges of process internals.

Samples, on a background thread (and on demand at /metrics scrape and
/status), the sizes that explain serving behavior but have no natural
increment site:

- holder shape: open indexes/frames/fragments, row-cache entries;
- device residency: HBM bytes used/budgeted, hit/miss/eviction counts
  (parallel.residency.device_cache);
- XLA compile cache: program-cache hits/misses, programs built, and
  wall seconds spent in first-call trace+compile
  (parallel.mesh.compile_stats — the counters that answer "is the
  cache hitting, does anything warm it");
- roaring container op counts by container kind
  (storage.roaring.op_counts), plus the live container mix — counts
  and resident bytes by kind (array/bitmap/run) aggregated from each
  fragment's epoch-cached container_stats — published as
  ``pilosa_roaring_containers_live`` / ``pilosa_roaring_container_bytes``;
- thread activity: live threads, and on-CPU threads via the
  utils.profiling sampler's idle-leaf filter;
- admission controller depth/in-flight.

Everything lands twice: as gauges/counters in the metrics registry
(``pilosa_runtime_*``, ``pilosa_holder_*``, ``pilosa_residency_*``,
``pilosa_compile_cache_*``) and as the ``runtime`` JSON block in
``/status``.
"""

from __future__ import annotations

import platform
import sys
import threading
import time
from typing import Optional

from ..sched import context as sched_context
from . import metrics as obs_metrics

DEFAULT_INTERVAL_S = 10.0

_build_info: Optional[dict] = None


def build_info() -> dict:
    """Build identity: package version, python, jax version, the jax
    backend platform with its device kind and count, and whether the
    two native host libraries built — the value block behind the
    ``pilosa_build_info`` gauge and the ``build`` block in /status.
    The jax fields read from the ALREADY-IMPORTED module only: a bare
    handler serving /status must not pay (or fail) a jax import, and
    ``default_backend()`` is only consulted once something else has
    initialized a backend."""
    global _build_info
    if _build_info is not None:
        return _build_info
    from .. import __version__
    jax_mod = sys.modules.get("jax")
    jax_version = getattr(jax_mod, "__version__", "") if jax_mod else ""
    backend = device_kind = ""
    device_count = 0
    if jax_mod is not None:
        try:
            backend = jax_mod.default_backend()
            devices = jax_mod.devices()
            device_kind = devices[0].device_kind
            device_count = len(devices)
        except Exception:  # noqa: BLE001 - backend init can fail off-TPU
            backend = "unavailable"
    labels = {"version": __version__,
              "python": platform.python_version(),
              "jax": jax_version or "unloaded",
              "backend": backend or "unloaded"}
    info = dict(labels)
    # Publish (and cache) only once jax is actually loaded: an early
    # /status on a bare handler must neither freeze "unloaded" for the
    # process nor leave a second, stale build_info series behind.
    if jax_mod is not None:
        from ..storage import native, native_ext
        info.update(deviceKind=device_kind, deviceCount=device_count,
                    native=native.available(),
                    nativeExt=native_ext.available())
        obs_metrics.BUILD_INFO.labels(**labels).set(1)
        _build_info = info
    return info


class RuntimeCollector:
    def __init__(self, holder=None, executor=None, admission=None,
                 registry=None, interval_s: float = DEFAULT_INTERVAL_S,
                 slo=None, profiler=None, history=None,
                 tenant_slo=None):
        self.holder = holder
        self.executor = executor
        self.admission = admission
        # SLO burn-rate trackers (obs.slo.SLOTracker and the
        # per-tenant obs.slo.TenantSLOTracker) and the continuous
        # profiler (obs.profile) — sampled/summarized on the same
        # cadence so /status carries both.
        self.slo = slo
        self.tenant_slo = tenant_slo
        self.profiler = profiler
        # Metric history (obs.history): one registry-wide sampling
        # pass per collector tick — AFTER the gauges above refresh, so
        # each tick's rings see this tick's sizes. The store guards
        # against the on-demand /status path double-sampling a tick.
        self.history = history
        self.registry = registry or obs_metrics.default_registry()
        self.interval_s = interval_s
        self._mu = threading.Lock()
        self._last: dict = {}
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run,
                                        name="pilosa-runtime-collector",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        """Stop AND join: callers close the metric history right
        after, and a collector thread still mid-collect would write
        a fresh history segment past the close."""
        self._stop.set()
        thread = self._thread
        if thread is not None \
                and thread is not threading.current_thread():
            thread.join(timeout=5.0)
        self._thread = None

    def _run(self) -> None:
        while (due := sched_context.timed_wait(
                self._stop, self.interval_s)) is not None:
            try:
                with sched_context.background_tick("runtime", due):
                    self.collect()
            except Exception:  # noqa: BLE001 - sampling must not kill serving
                pass

    # -- sampling ------------------------------------------------------------

    def collect(self) -> dict:
        """One sampling pass: update registry gauges, return (and
        retain for /status) the snapshot dict."""
        snap: dict = {"sampledAt": time.time()}
        snap["build"] = build_info()
        snap["holder"] = self._holder_sizes()
        snap["threads"] = self._thread_sample()
        snap["deviceBlockCache"] = self._residency()
        snap["compileCache"] = self._compile_cache()
        snap["roaringOps"] = self._roaring_ops()
        if self.admission is not None:
            adm = self.admission.snapshot()
            snap["admission"] = adm
            obs_metrics.ADMISSION_IN_FLIGHT.set(adm.get("inFlight", 0))
            for lane, depth in (adm.get("queued") or {}).items():
                obs_metrics.ADMISSION_QUEUE_DEPTH.labels(lane).set(depth)
        if self.executor is not None:
            snap["deviceFallbacks"] = getattr(self.executor,
                                              "device_fallbacks", 0)
            snap["costModelVetoes"] = getattr(self.executor,
                                              "cost_vetoes", 0)
        if self.slo is not None:
            try:
                snap["slo"] = self.slo.record()
            except Exception:  # noqa: BLE001 - visibility only
                pass
        if self.tenant_slo is not None:
            try:
                snap["tenantSlo"] = self.tenant_slo.record()
            except Exception:  # noqa: BLE001 - visibility only
                pass
        if self.profiler is not None:
            snap["profiler"] = self.profiler.snapshot()
        if self.history is not None:
            try:
                # Counted apart: it is the part of this tick that
                # grows with the number of series.
                with sched_context.background_tick("history"):
                    self.history.sample()
                snap["history"] = self.history.stats()
            except Exception:  # noqa: BLE001 - history must not break /status
                pass
        with self._mu:
            self._last = snap
        return snap

    def snapshot(self) -> dict:
        """Most recent sample (collecting one if none exists yet)."""
        with self._mu:
            last = self._last
        if not last:
            try:
                return self.collect()
            except Exception:  # noqa: BLE001 - visibility, not serving
                return {}
        return last

    # -- individual samplers -------------------------------------------------

    def _holder_sizes(self) -> dict:
        out = {"indexes": 0, "frames": 0, "fragments": 0,
               "cacheEntries": 0}
        # Container mix by kind (array/bitmap/run): counts + resident
        # bytes, from each fragment's per-epoch-cached stats walk —
        # "the mix shifts to runs" as gauges, not prose.
        kind_counts = {"array": 0, "bitmap": 0, "run": 0}
        kind_bytes = {"array": 0, "bitmap": 0, "run": 0}
        holder = self.holder
        if holder is None:
            return out
        try:
            indexes = dict(holder.indexes)
        except Exception:  # noqa: BLE001 - holder may be mid-close
            return out
        out["indexes"] = len(indexes)
        for idx in indexes.values():
            frames = dict(idx.frames)
            out["frames"] += len(frames)
            for frame in frames.values():
                for view in dict(frame.views).values():
                    frags = dict(view.fragments)
                    out["fragments"] += len(frags)
                    for frag in frags.values():
                        cache = getattr(frag, "cache", None)
                        if cache is not None:
                            try:
                                out["cacheEntries"] += len(cache)
                            except TypeError:
                                pass
                        try:
                            cs = frag.container_stats()
                        except Exception:  # noqa: BLE001 - mid-close
                            continue
                        for kind in kind_counts:
                            kind_counts[kind] += cs["counts"][kind]
                            kind_bytes[kind] += cs["bytes"][kind]
        out["containers"] = {"counts": kind_counts, "bytes": kind_bytes}
        obs_metrics.HOLDER_FRAGMENTS.set(out["fragments"])
        obs_metrics.HOLDER_CACHE_ENTRIES.set(out["cacheEntries"])
        for kind in kind_counts:
            obs_metrics.ROARING_CONTAINERS.labels(kind).set(
                kind_counts[kind])
            obs_metrics.ROARING_CONTAINER_BYTES.labels(kind).set(
                kind_bytes[kind])
        return out

    def _thread_sample(self) -> dict:
        from ..utils import profiling
        live = threading.active_count()
        try:
            on_cpu = len(profiling.collect_sample(include_idle=False))
        except Exception:  # noqa: BLE001 - interpreter-internal API
            on_cpu = 0
        obs_metrics.RUNTIME_THREADS.labels("live").set(live)
        obs_metrics.RUNTIME_THREADS.labels("on_cpu").set(on_cpu)
        return {"live": live, "onCpu": on_cpu}

    def _residency(self) -> dict:
        try:
            from ..parallel import residency
            snap = residency.device_cache().snapshot()
        except Exception:  # noqa: BLE001 - jax backend may be absent
            return {}
        obs_metrics.RESIDENCY_BYTES.labels("used").set(
            snap.get("usedBytes", 0))
        obs_metrics.RESIDENCY_BYTES.labels("budget").set(
            snap.get("budgetBytes", 0))
        return snap

    def _compile_cache(self) -> dict:
        try:
            from ..parallel import mesh as mesh_mod
            stats = mesh_mod.compile_stats()
        except Exception:  # noqa: BLE001 - mesh import can fail sans jax
            return {}
        obs_metrics.COMPILE_HITS.set_total(stats.get("hits", 0))
        obs_metrics.COMPILE_MISSES.set_total(stats.get("misses", 0))
        obs_metrics.COMPILE_SECONDS.set_total(
            stats.get("compileSeconds", 0.0))
        obs_metrics.COMPILE_PROGRAMS.set(stats.get("programs", 0))
        fair = mesh_mod.fair_dispatch_state()
        if fair is not None:
            stats = dict(stats)
            stats["fairDispatch"] = fair
        return stats

    def _roaring_ops(self) -> dict:
        try:
            from ..storage import roaring
            counts = roaring.op_counts()
        except Exception:  # noqa: BLE001 - visibility only
            return {}
        out = {}
        for (op, kind), n in counts.items():
            if n:
                obs_metrics.ROARING_OPS.labels(op, kind).set_total(n)
                out[f"{op}:{kind}"] = n
        return out
