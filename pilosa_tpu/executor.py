"""Executor: per-call PQL dispatch + cluster map-reduce over slices.

Reference: executor.go. Each call type maps/reduces over the slice axis:
Count sums per-slice counts, TopN merges per-slice pair lists (then
re-queries exact counts for the candidate ids, executor.go:273-310), bitmap
expressions fold per slice and the result segments stay sharded. Writes
route to every replica owner of the target slice and forward to remote
owners unless the query already carries the Remote flag
(executor.go:664-797).

Map-reduce (executor.go:1103-1236): slices group by owning node
(jump-hash placement, cluster.topology), one worker per node; a failed
node is filtered out and its slices re-mapped onto remaining replicas
until none are left. Local legs fan out slice-parallel.

TPU-first departure: the per-slice hot work (row materialization, set
algebra, counts) already runs through the device kernel layer inside
Fragment; the executor's local fan-out additionally batches whole-index
Count/TopN onto the device mesh via pilosa_tpu.parallel.mesh when the
expression shape allows it — same reduction tree, but the slice axis is a
mesh axis and the reduce is an XLA psum instead of a Python loop.
"""

from __future__ import annotations

import datetime as dt
import os
import threading
from collections import OrderedDict
import time
from concurrent.futures import (FIRST_COMPLETED, Future,
                                ThreadPoolExecutor, wait)
from dataclasses import dataclass
from typing import Callable, Optional

import numpy as np

from .cluster.topology import Cluster, Node, new_cluster
from .errors import (TIME_FORMAT, FrameNotFoundError, IndexNotFoundError,
                     PilosaError, QueryCancelledError, QueryDeadlineError,
                     QueryRequiredError, SliceUnavailableError)
from .obs import accounting as obs_accounting
from .obs import metrics as obs_metrics
from .plan import planner as plan_planner
from .plan import record as plan_record
from .plan import store as plan_store
from .sched import context as sched_context
from . import SLICE_WIDTH
from .models.view import VIEW_INVERSE, VIEW_STANDARD
from .pql.ast import Call, Query
from .pql.parser import _POINT_MUTATE_RE
from .pql.parser import parse as parse_pql
from .storage import bsi
from .storage.bitmap import Bitmap, BitmapSegment
from .storage.cache import Pair, pairs_sort
from .storage.fragment import TopOptions
from .utils import timequantum as tq

# Frame used when a call does not specify one (executor.go:35).
DEFAULT_FRAME = "general"


class _RoutedSlices(list):
    """The whole-index slice list of one route record
    (``Executor._route``). It carries its record, so every layer below
    ``_execute`` recognises a memoised route by the list it was handed;
    any list derived from it (a filter, a failover re-map, a peer's
    group) is a plain list and takes the per-slice walk. Shared by
    concurrent reads: never mutated."""

    __slots__ = ("route",)


# Lowest count used in a TopN when no threshold is given (executor.go:39).
MIN_THRESHOLD = 1

_WRITE_CALLS = ("SetBit", "ClearBit", "SetFieldValue", "SetRowAttrs",
                "SetColumnAttrs")


@dataclass
class ExecOptions:
    """Remote=True marks a query forwarded by another node: process only
    local slices and don't re-forward (executor.go:1290-1292).
    pod_local=True marks a pod-internal leg (parallel.pod): run the
    plain local path over the given slices — no pod dispatch, no
    pod-global collectives. ctx carries the query's lifecycle state
    (sched.context.QueryContext: deadline budget + cancel flag) — every
    fan-out layer checks it, remote legs inherit the REMAINING budget,
    and None (internal/maintenance callers) means unbounded.

    partial=True (the ``?partial=1`` degraded-read contract, fault
    subsystem): slices with NO reachable replica are skipped instead
    of failing the whole query; their ids accumulate in
    ``missing_slices`` (the handler reports them as the
    ``X-Pilosa-Partial`` response header)."""
    remote: bool = False
    pod_local: bool = False
    ctx: Optional[object] = None
    partial: bool = False
    missing_slices: Optional[list] = None


def _ran_here(fn, *args) -> Future:
    """``fn(*args)`` on the calling thread, as the finished future a
    pool would have handed back: whatever it raised is raised again
    where ``result()`` is read."""
    fut: Future = Future()
    try:
        fut.set_result(fn(*args))
    except BaseException as e:  # noqa: BLE001 - result() re-raises it
        fut.set_exception(e)
    return fut


def _needs_slices(calls: list[Call]) -> bool:
    # executor.go:1273-1289
    if not calls:
        return False
    return any(c.name not in _WRITE_CALLS for c in calls)


def _has_only_set_row_attrs(calls: list[Call]) -> bool:
    return bool(calls) and all(c.name == "SetRowAttrs" for c in calls)


def _parse_timestamp(c: Call, key: str = "timestamp"
                     ) -> Optional[dt.datetime]:
    v = c.args.get(key)
    if v is None:
        return None
    if isinstance(v, dt.datetime):
        return v
    try:
        return dt.datetime.strptime(v, TIME_FORMAT)
    except (TypeError, ValueError):
        raise PilosaError(f"invalid date: {v}")


_VISIT_PROBE_SLICES = 16


def _measure_host_visit_s() -> float:
    """What the host path pays for one leaf row of one slice before it
    reads a word (Calibration.host_visit_s). Timed on the executor's
    own host fan-out — fragment look-up, row extraction, one slice-pool
    task a slice, one native count a pair of rows — over a scratch
    index of near-empty rows, so that the byte term is nothing and the
    walk is all there is; a 2-leaf Count, the cheapest host form (three
    leaves and more fold a container at a time in Python and cost
    more a visit: the price is a floor). Each timed Count names rows no
    earlier one did, so no result cache can answer it, and runs with no
    query bound: the cost tree and stage clock of the query whose
    thread calibrates see none of it."""
    import tempfile

    from .models.holder import Holder

    n, reps = _VISIT_PROBE_SLICES, 4        # the first rep warms
    # one bit in every 65536-column container of every slice, as any
    # row of a real index with more than a handful of bits has
    per = SLICE_WIDTH >> 16
    n_rows = 2 * reps
    rows = np.repeat(np.arange(n_rows, dtype=np.uint64), n * per)
    cols = np.tile(np.arange(n * per, dtype=np.uint64)
                   * np.uint64(1 << 16), n_rows)
    best = float("inf")
    with sched_context.use(None), \
            tempfile.TemporaryDirectory(prefix="pilosa_visit_") as tmp:
        holder = Holder(tmp)
        holder.open()
        ex = None
        try:
            holder.create_index("w").create_frame("f").import_bits(
                rows, cols)
            ex = Executor(holder, host="probe", use_mesh=False,
                          result_cache_entries=0,
                          cluster_cache_entries=0)
            for rep in range(reps):
                q = ("Count(Intersect(Bitmap(frame=\"f\", rowID=%d),"
                     " Bitmap(frame=\"f\", rowID=%d)))"
                     % (2 * rep, 2 * rep + 1))
                t0 = time.perf_counter()
                ex.execute("w", q, list(range(n)))
                if rep:
                    best = min(best, time.perf_counter() - t0)
        finally:
            if ex is not None:
                ex.close()
            holder.close()
    return max(best / (2 * n), 1e-7)


class Executor:
    """Executes PQL queries against a Holder, fanning out across a Cluster.

    ``client`` is the node-to-node transport (cluster.client.Client); any
    object with ``execute_query(node, index, query, slices, remote)`` works
    — tests inject scripted fakes exactly like the reference's mock
    executor seam (handler.go:60-62).
    """

    def __init__(self, holder, host: str = "",
                 cluster: Optional[Cluster] = None, client=None,
                 max_workers: int = 16, use_mesh: Optional[bool] = None,
                 mesh_min_slices: Optional[int] = None, pod=None,
                 fault=None, gens=None,
                 result_cache_entries: Optional[int] = None,
                 result_cache_bits: Optional[int] = None,
                 cluster_cache_entries: Optional[int] = None,
                 gen_staleness_s: Optional[float] = None,
                 tenants=None):
        self.holder = holder
        self.host = host
        self.cluster = cluster or new_cluster([host])
        self.client = client
        # Cluster-wide generation knowledge (cluster.generations
        # GenerationMap, shared with every pooled Client): lets the
        # result caches key and validate slices owned ELSEWHERE. None
        # (bare executors, single node) keeps those paths local-only.
        self.gens = gens
        # Tenant policy (sched.tenants.TenantRegistry): partitions the
        # result-cache budgets per tenant (= index, both cache keys
        # lead with it) via each tenant's cache-share, so one tenant's
        # working set cannot evict everyone else's. None = the
        # pre-tenant single-pool behavior.
        self.tenants = tenants
        if gen_staleness_s is None:
            raw = os.environ.get("PILOSA_CLUSTER_GEN_STALENESS")
            if raw:
                try:
                    gen_staleness_s = float(raw)
                except ValueError:
                    from .utils.config import parse_duration
                    gen_staleness_s = parse_duration(raw)
        self._gen_staleness_s = gen_staleness_s  # None = map default
        # Fault-tolerance state (fault.FaultManager): _slices_by_node
        # orders replica owners by health and sinks open circuits, the
        # re-map path consults it instead of rediscovering a dead peer
        # per query, and remote legs hedge when configured. None keeps
        # the plain jump-hash-primary placement.
        self.fault = fault
        # Multi-host pod membership (parallel.pod.Pod) — None in the
        # ordinary single-process server. On the pod coordinator the
        # local leg fans out pod-wide (collectives for device-batched
        # Count/TopN, podLocal HTTP legs for everything else).
        self.pod = pod
        self.max_workers = max_workers
        if use_mesh is None:
            use_mesh = os.environ.get("PILOSA_TPU_MESH", "1") != "0"
        self.use_mesh = use_mesh
        if mesh_min_slices is None:
            mesh_min_slices = int(os.environ.get(
                "PILOSA_TPU_MESH_MIN_SLICES", "8"))
        # Below this many local slices the per-slice host path serves:
        # one device dispatch costs a host↔device sync that only pays
        # for itself on wide fan-outs.
        self.mesh_min_slices = mesh_min_slices
        # Materializing bitmap calls engage the device only past this
        # many leaf rows (config 2's wide-Union form); below it the
        # per-slice roaring merges win.
        self.mesh_min_leaves = int(os.environ.get(
            "PILOSA_TPU_MESH_MIN_LEAVES", "8"))
        # Calibrated device/host routing (parallel.costmodel): above the
        # static floor, a measured cost model can still veto the device
        # when the host path is a clear predicted win on this hardware.
        # Injectable for tests; PILOSA_TPU_COST_MODEL=0 disables.
        self.cost_model = None
        self._cost_model_enabled = os.environ.get(
            "PILOSA_TPU_COST_MODEL", "1") != "0"
        self._cost_margin = float(os.environ.get(
            "PILOSA_TPU_COST_MARGIN", "0.5"))
        # Deliberate host routings by the cost model (observability —
        # distinct from device_fallbacks, which count failures).
        self.cost_vetoes = 0
        # The timed legs running now (_timed_leg): a set, because add
        # and discard are atomic under the interpreter lock.
        self._timed_legs: set = set()
        self._mesh = None  # lazy: built on first device-batched call
        self._mesh_failed_until = None  # backoff after backend failure
        # /debug/vars.mesh: how often make_mesh gave this executor its
        # mesh, and how often it failed (each failure serves from the
        # host for _MESH_RETRY_S).
        self.mesh_builds = 0
        self.mesh_failures = 0
        # Device-fallback observability (a real kernel bug would
        # otherwise silently demote every query to the host path):
        # counted per executor, surfaced via stats + one-shot warning.
        self.device_fallbacks = 0
        self._fallback_warned = False
        # Persistent worker pools (created lazily on first fan-out).
        # Spawning a ThreadPoolExecutor per query cost more than the
        # whole host-side map at small fan-outs. Three tiers because a
        # task in one tier blocks on the tier below (node mapper →
        # pod legs → slice map); a single shared pool could deadlock.
        self._pools: dict[str, ThreadPoolExecutor] = {}
        self._pools_mu = threading.Lock()
        # Materialized bitmap-result residency (see _bitmap_result_key).
        # Bounds are configurable ([query] result-cache-* /
        # PILOSA_QUERY_RESULT_CACHE_*); the class attrs stay the
        # defaults for bare executors.
        self._bitmap_results: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._bitmap_results_mu = threading.Lock()
        if result_cache_entries is None:
            result_cache_entries = int(os.environ.get(
                "PILOSA_QUERY_RESULT_CACHE_ENTRIES",
                str(self._RESULT_CACHE_ENTRIES)))
        if result_cache_bits is None:
            result_cache_bits = int(os.environ.get(
                "PILOSA_QUERY_RESULT_CACHE_BITS",
                str(self._RESULT_CACHE_BITS)))
        self._result_cache_entries = result_cache_entries
        self._result_cache_bits = result_cache_bits
        # Coordinator hot-query result cache (the first cluster-wide
        # reuse of the generation machinery): merged read-query
        # results keyed by (index, PQL, slice set), validated on hit
        # by a /generations token round-trip per involved peer — so a
        # repeated resident chain over remote slices serves at ~RTT
        # floor instead of re-running the fan-out + fold. 0 disables.
        if cluster_cache_entries is None:
            cluster_cache_entries = int(os.environ.get(
                "PILOSA_QUERY_CLUSTER_CACHE_ENTRIES", "64"))
        self._cluster_cache_entries = cluster_cache_entries
        self._cluster_cache: "OrderedDict[tuple, dict]" = OrderedDict()
        self._cluster_cache_mu = threading.Lock()
        # Hit-validation probe budget (seconds): a probe is an
        # optimization, so a slow/stalled peer costs at most this
        # before the entry drops and the real fan-out (with its
        # failover machinery) answers.
        self._CLUSTER_PROBE_TIMEOUT_S = 1.0
        # Distributed TopN pushdown (ROADMAP item 3): remote legs run
        # the single-pass TopN over their own slices and the
        # coordinator merges partials per the reference two-phase
        # semantics. Off => the plain candidate fan-out path.
        self._topn_pushdown = os.environ.get(
            "PILOSA_TPU_TOPN_PUSHDOWN", "1") != "0"
        # Speculative hint memo: (index, frame) -> the last merged
        # candidate union (bounded), dispatched with pushdown legs so
        # the steady state needs ONE overlapped round trip. Purely
        # advisory — a stale or missing entry costs an extra round,
        # never correctness.
        self._topn_hint_memo: "OrderedDict[tuple, tuple]" = \
            OrderedDict()
        # Per-op write fast lane (see _execute_mutate_bit): (index,
        # frame, slice) -> (frame_obj, Fragment), validated per op by
        # identity of the CURRENT frame object and the fragment's
        # _open flag — a deleted or recreated frame closes its
        # fragments, which forces re-resolution.
        self._wfast_frag: dict[tuple, tuple] = {}
        # Cost-based planner (pilosa_tpu.plan): consulted once per
        # read query before the cluster-cache key — reorder,
        # short-circuit, CSE via the token-keyed subresult cache, and
        # per-subtree placement. The per-executor flag plus the module
        # switch (plan.record.set_enabled / PILOSA_TPU_PLANNER=0)
        # restore the unplanned dispatcher for A/B measurement.
        self.planner = plan_planner.Planner(holder,
                                            margin=self._cost_margin)
        self.planner_enabled = True
        # /debug/vars.routeMemo: device-lowered reads whose whole route
        # (slice list, ownership, slice→node groups, each leaf view's
        # fragment list) was reused from its record in the planner's
        # memo (hits) or resolved by the per-slice walk (misses), and
        # records or view entries dropped because a token moved.
        self.route_memo = {"hits": 0, "misses": 0, "invalidated": 0}
        self._route_mu = threading.Lock()
        # /debug/vars.legs: map-reduce fan-outs whose lone local leg
        # ran on the calling thread (inline) or that went to the
        # ``node`` pool (pooled). No lock: two threads may lose a
        # count between them, and a reader takes shares of these.
        self.legs = {"inline": 0, "pooled": 0}
        # /debug/vars.topn: with-source TopN reads by where their
        # counts came from (one device program, or the host walk), and
        # the candidate rows their first phase gave, summed. No lock,
        # like ``legs``.
        self.topn_src = {"srcDevice": 0, "srcHost": 0, "candidates": 0}
        # Per-fingerprint plan store behind GET /debug/plans (the
        # handler records finished coordinator queries into it).
        self.plan_store = plan_store.PlanStore()

    def _pool(self, tier: str) -> ThreadPoolExecutor:
        with self._pools_mu:
            pool = self._pools.get(tier)
            size = self.max_workers
            if tier == "hedge":
                # Primaries AND their hedge legs share this tier, and
                # every node-tier remote leg parks one primary here —
                # at 1× the node tier's size, hedge legs would queue
                # behind the very primaries they are racing (and a
                # queued primary's wait(hedge_s) would expire on queue
                # delay alone, firing spurious hedges under load).
                size *= 2
            if tier == "pod" and self.pod is not None:
                # Pod legs must all run concurrently — latency is
                # the max over legs, not the sum (the old per-query
                # pool sized itself to the leg count). If the peer set
                # has grown since the pool was built, grow with it: a
                # too-small pool serializes legs (no deadlock — pod
                # legs only block on the tier below — just latency).
                size = max(size, len(self.pod.peers))
            if pool is not None and pool._max_workers < size:
                # Don't shutdown(): a concurrent query may still hold a
                # reference and submit to it — shutdown would fail that
                # query with RuntimeError. Dropped pools drain naturally
                # (their idle threads exit when the pool is collected).
                self._pools.pop(tier)
                pool = None
            if pool is None:
                pool = self._pools[tier] = ThreadPoolExecutor(
                    max_workers=size,
                    thread_name_prefix=f"pilosa-exec-{tier}")
            return pool

    def close(self) -> None:
        """Shut down the worker pools (idempotent; the executor remains
        usable afterwards — pools are recreated on demand)."""
        with self._pools_mu:
            pools, self._pools = dict(self._pools), {}
        for pool in pools.values():
            pool.shutdown(wait=False, cancel_futures=True)

    def __del__(self):
        # Idle pool threads also exit when the executor is collected
        # (worker threads hold only a weakref to their pool), but bare
        # Executors that stay referenced would otherwise pin threads
        # for process lifetime — reclaim eagerly. Swallow everything:
        # at interpreter shutdown, pool internals may be torn down.
        try:
            self.close()
        except Exception:
            pass

    def _note_device_fallback(self, where: str, exc: Exception) -> None:
        self.device_fallbacks += 1
        stats = getattr(self.holder, "stats", None)
        if stats is not None:
            stats.count("deviceFallback", 1)
        if not self._fallback_warned:
            self._fallback_warned = True
            import logging
            logging.getLogger("pilosa_tpu.executor").warning(
                "device mesh path failed in %s (%s: %s); falling back to "
                "the host per-slice path — further fallbacks are counted "
                "but not logged", where, type(exc).__name__, exc)

    # Seconds to serve host-side before re-probing a failed device
    # backend (a server started while the device was unavailable
    # should pick it back up without a restart).
    _MESH_RETRY_S = 300.0

    def _mesh_backoff_active(self) -> bool:
        """True inside the backoff window after a device-backend
        failure. Device-eligible gates consult this before compiling a
        device expr so a meshless host serves the backoff window with
        zero discarded work (the compile is cheap, but it is pure waste
        when _mesh_or_none is known to return None)."""
        if self._mesh is not None or self._mesh_failed_until is None:
            return False
        return time.monotonic() < self._mesh_failed_until

    def _mesh_or_none(self):
        if not self.use_mesh:
            return None
        if self._mesh is None:
            if self._mesh_backoff_active():
                return None  # inside the backoff window: host path
            try:
                from .parallel import mesh as mesh_mod
                self._mesh = mesh_mod.make_mesh()
                self.mesh_builds += 1
                self._mesh_failed_until = None
                # Failure is cyclic under retry (outage → recovery →
                # outage); re-arm the one-shot log for the next one.
                self._fallback_warned = False
            except Exception as e:  # noqa: BLE001 - backend unavailable
                self.mesh_failures += 1
                self._mesh_failed_until = (time.monotonic()
                                           + self._MESH_RETRY_S)
                self._note_device_fallback("make_mesh", e)
                return None
        return self._mesh

    def mesh_state(self) -> Optional[dict]:
        """/debug/vars.mesh: the one mesh this executor's device
        programs run on — its (rows, slices) shape and device count, how
        often it was built and how often building it failed, and the
        device programs dispatched so far (process-wide: parallel.mesh
        counts them). None before the first device call asked for a
        mesh."""
        mesh = self._mesh
        if mesh is None and not self.mesh_failures:
            return None
        from .parallel import mesh as mesh_mod
        shape = None if mesh is None else list(mesh.devices.shape)
        return {"shape": shape,
                "devices": 0 if mesh is None else int(mesh.devices.size),
                "builds": self.mesh_builds,
                "failures": self.mesh_failures,
                "programsRun": mesh_mod.programs_run()}

    # -- entry point (executor.go:62-143) ------------------------------------

    def execute_partial(self, index: str, query,
                        slices: Optional[list[int]] = None,
                        opt: Optional[ExecOptions] = None
                        ) -> tuple[list, Optional[Exception]]:
        """Like execute(), but an exception mid-query returns
        (results-so-far, error) instead of raising — callers that
        combine independent call streams (the HTTP pipelined batch
        lane) can then map the prefix faithfully: calls before the
        error were durably applied, calls after it never ran. An
        all-SetRowAttrs query is refused (its bulk path applies
        non-positionally, so a prefix would be meaningless)."""
        if isinstance(query, str):
            query = parse_pql(query)
        if _has_only_set_row_attrs(query.calls):
            raise PilosaError("execute_partial: bulk attrs unsupported")
        results: list = []
        try:
            self.execute(index, query, slices, opt, _partial_out=results)
        except Exception as e:  # noqa: BLE001 - contract: return it
            return results, e
        return results, None

    def execute(self, index: str, query, slices: Optional[list[int]] = None,
                opt: Optional[ExecOptions] = None,
                _partial_out: Optional[list] = None) -> list:
        opt = opt or ExecOptions()
        if opt.ctx is None:
            return self._execute(index, query, slices, opt, _partial_out)
        # Lifecycle-bound query: check the budget up front and bind the
        # context to this thread so layers without a ctx argument (the
        # mesh device dispatch reached from this thread, e.g. the
        # batched-Count lane) can check it too.
        opt.ctx.check()
        with sched_context.use(opt.ctx):
            return self._execute(index, query, slices, opt, _partial_out)

    def _execute(self, index: str, query, slices: Optional[list[int]],
                 opt: ExecOptions,
                 _partial_out: Optional[list] = None) -> list:
        if not index:
            raise PilosaError("index required")
        if isinstance(query, str):
            # Fused point-mutation lane (ISSUE 8): the per-op serving
            # string goes regex -> cached fragment -> set_bit in one
            # step, skipping AST construction and call dispatch —
            # those cost ~3x the mutate itself at per-op rates. Any
            # miss (cold cache, unusual frame/cluster shape) falls
            # through to the identical generic path, which also
            # populates the cache.
            if _partial_out is None and self.pod is None:
                m = _POINT_MUTATE_RE.match(query)
                if m is not None:
                    r = self._point_mutate_fast(index, m, opt)
                    if r is not None:
                        return r
            query = parse_pql(query)
        if not isinstance(query, Query):
            raise QueryRequiredError("query required")

        calls = query.calls
        if (len(calls) == 1 and _partial_out is None
                and calls[0].name in ("SetBit", "ClearBit")):
            # Single point mutation — the per-op serving shape. Skip
            # the multi-call preamble (slice enumeration, batch-run
            # probes): a write call needs no slice list, and
            # _execute_mutate_bit owns its whole contract including
            # its own fast lane.
            if opt.ctx is not None:
                opt.ctx.check()
            return [self._execute_mutate_bit(
                index, calls[0], opt, calls[0].name == "SetBit")]

        needs = _needs_slices(query.calls)
        inverse_slices: list[int] = []
        column_label = "columnID"
        # Inverse-slice substitution happens only when WE computed the
        # slice lists. A forwarded (remote) query arrives with the exact
        # slice ids the coordinator already selected — replacing them
        # would wrongly empty inverse legs.
        computed_slices = not slices
        if not slices and needs:
            idx = self.holder.index(index)
            if idx is None:
                raise IndexNotFoundError(index)
            slices = self._whole_slices(index, idx)
            inverse_slices = list(range(idx.max_inverse_slice() + 1))
            column_label = idx.column_label
        slices = slices or []

        if _has_only_set_row_attrs(query.calls):
            return self._execute_bulk_set_row_attrs(index, query.calls, opt)

        # Cost-based planning (pilosa_tpu.plan): read queries are
        # rewritten BEFORE the cluster-cache key is computed, so the
        # cache keys the planned canonical form. Planning failure is
        # never a query failure — the original tree executes.
        plan_rec = None
        if needs and slices:
            with sched_context.stage("plan"):
                query, plan_rec = self._maybe_plan(index, query, slices,
                                                   opt)

        # Coordinator hot-query result cache (cluster.generations):
        # repeated read queries over a distributed slice set serve at
        # ~RTT floor — one /generations token probe per involved peer
        # instead of the full fan-out + fold — with a token mismatch
        # (any replica took a write) invalidating the entry.
        cluster_key = pre_tokens = None
        if _partial_out is None:
            cluster_key = self._cluster_cache_key(index, query, slices,
                                                  opt)
            if cluster_key is not None:
                hit = self._cluster_cache_lookup(cluster_key, index,
                                                 opt)
                if hit is not None:
                    return hit
                pre_tokens = self._cluster_cache_snapshot(index,
                                                          slices)

        results = _partial_out if _partial_out is not None else []
        i = 0
        while i < len(query.calls):
            if opt.ctx is not None:
                opt.ctx.check()  # between calls of a multi-call query
            # Consecutive device-compilable calls (Counts, exact-count
            # TopNs) fuse into ONE device program — the whole multi-op
            # tree pays one dispatch (one sync), not one per call.
            batch = self._device_batch_run(index, query.calls, i,
                                           slices, opt)
            if batch is not None:
                batch_results, n = batch
                results.extend(batch_results)
                i += n
                continue
            # Consecutive SetBit/ClearBit calls batch into one native
            # crossing + WAL group-commit per touched fragment.
            wbatch = self._mutate_batch_run(index, query.calls, i, opt)
            if wbatch is not None:
                bools, n = wbatch
                results.extend(bools)
                i += n
                continue
            call = query.calls[i]
            call_slices = slices
            if call.supports_inverse() and needs and computed_slices:
                frame_name = call.args.get("frame") or DEFAULT_FRAME
                frame = self.holder.frame(index, frame_name)
                if frame is None:
                    raise FrameNotFoundError(frame_name)
                if call.is_inverse(frame.row_label, column_label):
                    call_slices = inverse_slices
            analyze_call = (plan_rec is not None
                            and (plan_rec.sample or plan_rec.analyze))
            if analyze_call:
                t_call = time.perf_counter()
            r = self._execute_call(index, call, call_slices, opt)
            if analyze_call:
                self._plan_record_actual(call, r,
                                         time.perf_counter() - t_call,
                                         plan_rec)
            results.append(r)
            i += 1
        if cluster_key is not None:
            self._cluster_cache_store(cluster_key, index, slices,
                                      results, pre_tokens)
        return results

    def _execute_call(self, index: str, c: Call, slices: list[int],
                      opt: ExecOptions):
        # executor.go:146-170
        if c.name == "ClearBit":
            return self._execute_clear_bit(index, c, opt)
        if c.name == "Count":
            return self._execute_count(index, c, slices, opt)
        if c.name == "SetBit":
            return self._execute_set_bit(index, c, opt)
        if c.name == "SetRowAttrs":
            self._execute_set_row_attrs(index, c, opt)
            return None
        if c.name == "SetColumnAttrs":
            self._execute_set_column_attrs(index, c, opt)
            return None
        if c.name == "TopN":
            return self._execute_top_n(index, c, slices, opt)
        if c.name in ("Sum", "Min", "Max"):
            return self._execute_field_aggregate(index, c, slices, opt)
        if c.name == "SetFieldValue":
            return self._execute_set_field_value(index, c, opt)
        return self._execute_bitmap_call(index, c, slices, opt)

    # -- cost-based planning (pilosa_tpu.plan) -------------------------------

    def _maybe_plan(self, index: str, query: Query, slices: list[int],
                    opt: ExecOptions):
        """Plan a read query: returns (query', PlanRecord) — the
        planned calls when planning applies, the original (query,
        None) otherwise. The planner builds every planned Call with
        its plan node as ``_plan_node`` (the per-slice hooks run on
        mapper-pool threads where no request context is visible, so
        the hint travels with the call itself) and the record attaches
        to ``ctx.plan`` for the observability plane. A profiled
        request (?profile=1) is planned in full, past the shape
        entries, and records every node."""
        if (self.planner is None or not self.planner_enabled
                or not plan_record.enabled()):
            return query, None
        if query.write_calls():
            return query, None
        try:
            all_local = self._owns_all_slices(index, slices)
        except Exception:  # noqa: BLE001 - locality is advisory here
            all_local = False
        ctx = opt.ctx
        profile = ctx is not None and bool(getattr(ctx, "profile", False))
        try:
            if profile:
                planned, rec = self.planner.plan_query(
                    index, query.calls, slices, all_local=all_local,
                    node=self.host)
            else:
                route = getattr(slices, "route", None)
                planned, rec = self.planner.plan_query_cached(
                    index, query.calls, slices, all_local=all_local,
                    node=self.host,
                    slices_key=(route["skey"] if route is not None
                                else None))
        except Exception:  # noqa: BLE001 - planning never fails a query
            return query, None
        if ctx is not None:
            rec.analyze = profile
            ctx.plan = rec
        return Query(planned), rec

    def _plan_record_actual(self, call: Call, result, elapsed_s: float,
                            rec: plan_record.PlanRecord) -> None:
        """ANALYZE half: stamp per-call wall time always, and actual
        cardinality where it is free (Count results) or requested
        (?profile=1 pays one count() walk of the result)."""
        node = getattr(call, "_plan_node", None)
        if node is None:
            return
        node.actual_s = elapsed_s
        try:
            if isinstance(result, int) and not isinstance(result, bool):
                plan_planner._observe_misestimate(node, result)
            elif rec.analyze and hasattr(result, "count"):
                plan_planner._observe_misestimate(node, result.count())
        except Exception:  # noqa: BLE001 - observability only
            pass

    def explain(self, index: str, query,
                slices: Optional[list[int]] = None) -> dict:
        """EXPLAIN-only (?plan=1): plan the query without executing
        and return the plan tree."""
        if isinstance(query, str):
            query = parse_pql(query)
        if not isinstance(query, Query):
            raise QueryRequiredError("query required")
        if query.write_calls():
            raise PilosaError("cannot EXPLAIN a write query")
        if not slices:
            idx = self.holder.index(index)
            if idx is None:
                raise IndexNotFoundError(index)
            slices = list(range(idx.max_slice() + 1))
        try:
            all_local = self._owns_all_slices(index, slices)
        except Exception:  # noqa: BLE001
            all_local = False
        return self.planner.explain(index, query.calls, slices,
                                    all_local=all_local)

    def _owns_all_slices(self, index: str, slices: list[int]) -> bool:
        """True when THIS node holds a replica of every slice the query
        touches — the ownership gate that keeps the single-node fast
        paths (materialized-result residency, the fused device count
        fold, single-pass TopN) live on multi-node clusters for
        locally-owned work (the old ``nodes != 1`` gates disabled them
        the moment a second node joined, even with replica_n covering
        everything). Correctness rests on the write
        path: every SetBit/import/anti-entropy leg applies to EVERY
        replica owner, so an owned slice's local fragment (and its
        mutation generation, for the residency keys) tracks all
        writes. During an elastic resize this is READ authority, not
        the write-accept union — a stream target's copy is incomplete
        until the flip, so it must not claim local fast paths for a
        moving slice (cluster.topology.read_allowed)."""
        route = getattr(slices, "route", None)
        if route is not None:
            return route["all_local"]  # this walk's answer, memoised
        q = getattr(self.holder, "quarantine", None)
        if q is not None and len(q) and any(
                q.slice_blocked(index, s) for s in slices):
            # Storage integrity: a quarantined local copy must never
            # claim a fast path — its bytes (or its fresh post-reset
            # replacement) cannot be trusted to answer.
            return False
        tier = getattr(self.holder, "tier", None)
        if tier is not None and any(
                tier.slice_blocked(index, s) for s in slices):
            # Tiered storage: a blob-tier fragment whose cold fetch
            # keeps failing has NO local bytes — the slice must fail
            # over (or degrade per the partial contract), same as a
            # quarantine.
            return False
        if (len(self.cluster.nodes) == 1
                and self.cluster.resize is None):
            return True
        host = self.host
        allowed = self.cluster.read_allowed
        return all(allowed(host, index, s) for s in slices)

    # -- the route record (a read's route, resolved once per generation) -----

    def _route_note(self, kind: str, invalidated: int = 0) -> None:
        with self._route_mu:
            self.route_memo[kind] += 1
            self.route_memo["invalidated"] += invalidated

    def _placement_token(self) -> Optional[tuple]:
        """What a memoised slice→node grouping rests on, or None while
        anything may steer a read away from its placement-order owner:
        a resize in flight, a quarantined or tier-blocked slice, a
        circuit that is not closed or hedging (fault.steering), a pod.
        Node identity, not equality: a node replaced by its equal is
        another node."""
        cl = self.cluster
        if cl.resize is not None or self.pod is not None:
            return None
        q = getattr(self.holder, "quarantine", None)
        if q is not None and len(q):
            return None
        tier = getattr(self.holder, "tier", None)
        if tier is not None and tier._blocked_slices:
            return None
        if self.fault is not None and self.fault.steering():
            return None
        return (cl.epoch, cl.replica_n, cl.partition_n, cl.hasher,
                self.host, tuple(map(id, cl.nodes)))

    def _whole_slices(self, index: str, idx) -> list[int]:
        """Every slice of the index, as a read that names none asks:
        the route record's own list where a route may be memoised."""
        n = idx.max_slice() + 1
        route = self._route(index, idx, n)
        return route["slices"] if route is not None else list(range(n))

    def _route(self, index: str, idx, n: int) -> Optional[dict]:
        """The route record of a whole-index read over ``n`` slices:
        ``{slices, skey, all_local, groups, views}`` — the slice list,
        its O(1) name in residency keys, _owns_all_slices' answer,
        _slices_by_node's grouping over the whole cluster, and for each
        leaf view met so far its ((uid, generation), fragment list).
        It lives in the planner's memo (one LRU, one validity rule:
        tokens compared for equality) and is reused until the
        placement token, the Index object or the slice count differs —
        then it is dropped, counted, and resolved again by the walk,
        which stays the slow path and the tests' oracle. View entries
        are confirmed a leaf (_view_token). None = no memo for this
        read (something steers reads away, no planner, or the grouping
        depends on health order): the walk serves it."""
        planner = self.planner
        if planner is None:
            return None
        key = ("route", index)
        placement = self._placement_token()
        ent = planner.memo_get(key)
        if ent is not None:
            if (placement is not None and ent["idx"] is idx
                    and len(ent["slices"]) == n
                    and ent["placement"] == placement):
                return ent
            planner.memo_drop(key, ent)
            self._route_note("invalidated")
        if placement is None:
            return None
        slices = _RoutedSlices(range(n))
        slices.route = None  # the walks below must not see a record
        nodes = list(self.cluster.nodes)
        try:
            groups = self._slices_by_node(nodes, index, slices)
        except SliceUnavailableError:
            return None
        local = all(node.host == self.host for node, _ in groups)
        if (self.fault is not None and not local
                and min(self.cluster.replica_n, len(nodes)) > 1):
            # Remote replicas are ranked by health score, which moves
            # without any token moving: keep the walk.
            return None
        all_local = self._owns_all_slices(index, slices)
        if self._placement_token() != placement:
            return None  # it moved under the walk: resolve again later
        ent = {"idx": idx, "placement": placement, "nodes": nodes,
               "slices": slices, "skey": ("r", 0, n),
               "all_local": all_local, "views": {},
               # A group that is the whole list IS the record's list,
               # so the leg that serves it finds the record too.
               "groups": [(node, slices if len(g) == n else g)
                          for node, g in groups]}
        slices.route = ent
        planner.memo_put(key, ent)
        return ent

    # -- coordinator hot-query result cache (cluster.generations) -----------

    def _share_cached(self, r):
        """COW/shallow handout of one cached query result."""
        if isinstance(r, Bitmap):
            return self._share_result(r)
        if isinstance(r, list):
            return list(r)
        return r

    def _cluster_cache_key(self, index: str, query: Query,
                           slices: list[int],
                           opt: ExecOptions) -> Optional[tuple]:
        """(index, PQL, slice set) when this query is cluster-cache
        eligible: a coordinator-side read over a slice set NOT fully
        owned here (the covered case belongs to the local fast
        paths), with a generation map + probe-capable client to
        validate against. Declines: top-level Bitmap calls (their
        results carry row/column ATTRS, and attribute writes don't
        bump fragment generations — a cached copy could serve stale
        attrs) and attr-filtered TopN forms (same blind spot), and
        anything inverse-shaped at the top level (it swaps in the
        inverse slice list, which the per-slice token snapshot
        doesn't span)."""
        if (self._cluster_cache_entries <= 0 or self.gens is None
                or self.client is None
                or not hasattr(self.client, "generations")
                or self.pod is not None or opt.remote or opt.partial
                or not slices or len(self.cluster.nodes) < 2
                or self.cluster.resize is not None):
            # An in-flight resize declines caching outright: moving
            # slices' serving peers are in flux and a token snapshot
            # cannot attribute results to one placement.
            return None
        for call in query.calls:
            if (call.name in _WRITE_CALLS or call.name == "Bitmap"
                    or call.args.get("filters")):
                return None
        if self._owns_all_slices(index, slices):
            return None
        # The placement epoch is part of the key: after a resize flips,
        # a moved slice is served by a DIFFERENT peer whose fresh uid
        # must never validate an entry cached under the old owner's
        # tokens (the old owner's copy freezes and would validate
        # forever).
        return (index, str(query), tuple(slices), self.cluster.epoch)

    def _cluster_cache_lookup(self, key: tuple, index: str,
                              opt: ExecOptions) -> Optional[list]:
        with self._cluster_cache_mu:
            ent = self._cluster_cache.get(key)
        if ent is None:
            obs_metrics.CLUSTER_CACHE_REQUESTS.labels("miss").inc()
            return None
        if self._cluster_cache_validate(ent, index, opt):
            with self._cluster_cache_mu:
                if key in self._cluster_cache:
                    self._cluster_cache.move_to_end(key)
            obs_metrics.CLUSTER_CACHE_REQUESTS.labels("hit").inc()
            obs_accounting.note_result_cache_hit(opt.ctx)
            return [self._share_cached(r) for r in ent["results"]]
        with self._cluster_cache_mu:
            self._cluster_cache.pop(key, None)
        obs_metrics.CLUSTER_CACHE_REQUESTS.labels("invalidated").inc()
        return None

    def _cluster_cache_validate(self, ent: dict, index: str,
                                opt: ExecOptions) -> bool:
        """True iff every generation token the entry was cached under
        still holds: local slices against live fragments, remote
        slices against a fresh /generations probe of the peer that
        served them (~RTT, the whole point). Any mismatch or
        unreachable peer reads as invalid — never a stale answer."""
        from .cluster import generations as gens_mod
        for s, toks in ent["local"].items():
            if gens_mod.slice_tokens(self.holder, index, s) != toks:
                return False
        remote: dict = ent["remote"]
        if not remote:
            return True
        ctx = opt.ctx
        # The probe is an OPTIMIZATION: bound it tightly, far below
        # the query budget — a stalled peer must cost at most this
        # before the real fan-out (which owns failover) takes over. A
        # probe timing out is a failed validation, NOT the query's
        # deadline; ctx.check() below re-raises only when the query
        # itself is actually dead.
        timeout = self._CLUSTER_PROBE_TIMEOUT_S
        if ctx is not None:
            remaining = ctx.remaining()
            if remaining is not None:
                timeout = min(timeout, remaining)

        def probe(peer, entry):
            got = self.client.generations(index, sorted(entry),
                                          host=peer,
                                          deadline_s=timeout)
            return all(got.get(s) == toks
                       for s, toks in entry.items())

        try:
            items = list(remote.items())
            if len(items) == 1:
                return probe(*items[0])
            pool = self._pool("node")
            futs = [pool.submit(probe, p, e) for p, e in items]
            ok = True
            try:
                for f in futs:
                    if not f.result():
                        ok = False
            finally:
                pending = [f for f in futs if not f.cancel()]
                if pending:
                    wait(pending)
            return ok
        except (QueryDeadlineError, QueryCancelledError):
            if ctx is not None:
                ctx.check()  # the QUERY is dead → propagate
            return False  # only the bounded probe expired: recompute
        except Exception:  # noqa: BLE001 - unreachable peer: recompute
            return False

    def _cluster_cache_snapshot(self, index: str,
                                slices: list[int]) -> Optional[dict]:
        """Pre-execution token snapshot: live fragment tokens for
        locally-owned slices, and for remote slices the map's
        freshest-known (peer, tokens) — from the PREVIOUS exchange
        with the peer. A remote slice the map has never seen returns
        None (the query can't be cached this round; its own legs
        populate the map for the next one)."""
        from .cluster import generations as gens_mod
        # READ authority (see _bitmap_result_key): a moved slice this
        # node merely write-accepts during the post-resize grace must
        # snapshot the SERVING owner's tokens, never the local frozen
        # copy's.
        owns = self.cluster.read_allowed
        local: dict = {}
        remote: dict = {}
        for s in slices:
            if owns(self.host, index, s):
                local[s] = gens_mod.slice_tokens(self.holder, index, s)
                continue
            got = self.gens.newest(index, s)
            if got is None:
                return None
            peer, toks, _ts = got
            # The freshest map entry can belong to a peer that no
            # longer SERVES the slice (an old owner whose copy froze
            # at a resize finalize): an entry snapshotted under its
            # tokens would validate forever. Only read-authoritative
            # peers key cache entries; otherwise the query stays
            # uncached this round.
            if not self.cluster.read_allowed(peer, index, s):
                return None
            remote.setdefault(peer, {})[s] = dict(toks)
        return {"local": local, "remote": remote}

    def _cluster_cache_store(self, key: tuple, index: str,
                             slices: list[int], results: list,
                             pre: Optional[dict]) -> None:
        """Cache a completed read's merged results under the
        PRE-EXECUTION token snapshot, and only when the tokens are
        STABLE across the query (post-execution state identical): a
        generation that moved mid-query — a write racing the legs'
        reads, whichever side of them it landed on — means the
        results can't be attributed to one token state, so they stay
        uncached rather than risk a snapshot that validates forever
        against data the legs never saw (review finding). The stable
        case is exactly the one where the legs' reads provably fall
        inside an unchanged-generation window."""
        if pre is None:
            return
        bits = 0
        for r in results:
            if isinstance(r, Bitmap):
                bits += r.count()
        if bits > self._result_cache_bits:
            return
        post = self._cluster_cache_snapshot(index, slices)
        if post != pre:
            return
        ent = {"results": [self._share_cached(r) for r in results],
               "local": pre["local"], "remote": pre["remote"]}
        with self._cluster_cache_mu:
            cache = self._cluster_cache
            cache[key] = ent
            cache.move_to_end(key)
            # Per-tenant quota first (tenant = key[0], the index):
            # a tenant past its share evicts ITS OWN oldest entries,
            # never another tenant's.
            share = self._cache_share(key[0])
            if share < 1.0:
                cap = max(1, int(self._cluster_cache_entries * share))
                mine = [k for k in cache if k[0] == key[0]]
                for k in mine[:max(0, len(mine) - cap)]:
                    cache.pop(k, None)
            while len(cache) > self._cluster_cache_entries:
                cache.popitem(last=False)

    def on_resize_change(self, moved_fn=None) -> None:
        """Called on every resize transition this node observes
        (prepare / flip / finalize / abort — server.receive_message).
        Drops cached artifacts whose placement assumptions a resize
        breaks: the write fast-lane fragment cache (its single-node
        precondition), and — given ``moved_fn(index, slice) -> bool``
        — every result-residency and cluster-cache entry touching a
        moved slice (ISSUE 12 satellite: a moved slice served by a
        new peer with a fresh uid must never validate a stale entry;
        the epoch baked into both key shapes is the backstop, this is
        the eager flush)."""
        self._wfast_frag.clear()
        if moved_fn is None:
            return
        with self._bitmap_results_mu:
            for key in [k for k in self._bitmap_results
                        if any(moved_fn(k[0], s) for s in k[2])]:
                self._bitmap_results.pop(key, None)
        with self._cluster_cache_mu:
            for key in [k for k in self._cluster_cache
                        if any(moved_fn(k[0], s) for s in k[2])]:
                self._cluster_cache.pop(key, None)

    # -- bitmap expressions (executor.go:192-570) ----------------------------

    # Materialized-result residency: completed
    # Union/Intersect/Difference results stay cached, keyed by
    # (expression, per-fragment generations), so a repeated chain pays
    # zero re-fold and zero repack — the reference's own
    # lazy-materialization trick is its COW segments (bitmap.go:384-392);
    # this is the same idea one level up. Bounded by entries AND total
    # cached bits.
    _RESULT_CACHE_ENTRIES = 8
    _RESULT_CACHE_BITS = 32 << 20

    def _primary_owner_host(self, index: str, slice: int
                            ) -> Optional[str]:
        """The replica owner _slices_by_node would consult first for
        this slice (fault-ordered when a fault manager is attached) —
        the peer whose generation tokens a remote-slice cache key
        should embed, since it is the peer most likely to serve the
        recompute."""
        owners = self.cluster.fragment_nodes(index, slice)
        if not owners:
            return None
        if self.fault is not None and len(owners) > 1:
            owners = self.fault.order_nodes(owners, local=self.host)
        return owners[0].host

    def _bitmap_result_key(self, index: str, c: Call,
                           slices: list[int],
                           compiled_out: Optional[list] = None):
        """Cache key embedding every input fragment's mutation
        generation, or None when the call/topology isn't cacheable.
        Locally-owned slices key on the live fragment's (uid,
        generation) (every replica-fanned write bumps it); slices
        owned ELSEWHERE key on the owner's tokens from the coordinator
        generation map (cluster.generations) within the bounded
        staleness window — the map refreshes on every exchange with
        the peer (query legs, import acks, probes), so a write routed
        through this coordinator invalidates on its own response and
        only out-of-band writes ride the staleness bound. An unknown
        or stale token means uncached, never a guess. The compiled
        (expr, leaves) is appended to ``compiled_out`` so the device
        fold reuses it instead of re-walking the call tree
        (1000-child Unions pay the walk once, review r5)."""
        if c.name not in ("Union", "Intersect", "Difference"):
            return None
        if self.pod is not None:
            return None
        if self.cluster.resize is not None:
            # In-flight resize: moving slices' serving peers are in
            # flux (double-reads, mid-flip ownership) — uncached until
            # the epoch settles.
            return None
        owner_of: dict[int, str] = {}
        if len(self.cluster.nodes) > 1:
            # READ authority, not the write-accept union: inside the
            # post-resize grace window an old owner still write-
            # ACCEPTS a moved slice, but its copy no longer receives
            # single-path writes — keying on the frozen local fragment
            # would validate stale results forever (caught by the
            # resize verify drive).
            owns = self.cluster.read_allowed
            host = self.host
            for s in slices:
                if owns(host, index, s):
                    continue
                if self.gens is None:
                    return None  # invisible generations: uncached
                peer = self._primary_owner_host(index, s)
                if peer is None or peer == host:
                    return None
                owner_of[s] = peer
        leaves: list[tuple] = []
        expr = self._compile_device_expr(index, c, leaves)
        if expr is None or not leaves:
            return None
        if compiled_out is not None:
            compiled_out.append((expr, leaves))
        if len(leaves) * len(slices) > (1 << 16):
            return None  # key construction would outweigh the win
        gens = []
        for frame, view, _row in leaves:
            for s in slices:
                peer = owner_of.get(s)
                if peer is not None:
                    tok = self.gens.token(
                        peer, index, frame, view, s,
                        max_age_s=self._gen_staleness_s)
                    if tok is None:
                        return None  # unknown/stale: uncached
                    gens.append((peer, tok[0], tok[1]))
                    continue
                f = self.holder.fragment(index, frame, view, s)
                gens.append(("", f.device.uid, f.device.generation)
                            if f is not None else ("", 0, 0))
        # Epoch in the key: a slice that moved in a resize is served
        # by a different peer afterwards — entries keyed under the old
        # epoch's owners must never match post-flip lookups. The leaf
        # rows are in the key too: ``expr`` names leaves by position
        # and ``gens`` names fragments, so without them the same fold
        # over OTHER rows of the same frames would hit.
        return (index, expr, tuple(slices), tuple(gens), tuple(leaves),
                self.cluster.epoch)

    def _share_result(self, bm: Bitmap) -> Bitmap:
        """COW handout of a cached result (mutating callers copy,
        never the cached object)."""
        out = Bitmap()
        out.attrs = dict(bm.attrs)
        for seg in bm.segments:
            out.segments.append(BitmapSegment(seg.data.shared(),
                                              seg.slice, False))
        return out

    def _cache_share(self, tenant: str) -> float:
        """The fraction of each cache budget this tenant (= index) may
        occupy — 1.0 without a tenant registry (single pool)."""
        if self.tenants is None:
            return 1.0
        return self.tenants.policy(tenant).cache_share

    def _result_cache_put(self, key, bm: Bitmap) -> None:
        bits = bm.count()
        share = self._cache_share(key[0])
        tenant_budget = int(self._result_cache_bits * share)
        if bits > min(self._result_cache_bits, tenant_budget):
            return
        evicted_n = 0
        with self._bitmap_results_mu:
            cache = self._bitmap_results
            cache[key] = (bm, bits)
            cache.move_to_end(key)
            if share < 1.0:
                # Per-tenant byte quota: the inserting tenant evicts
                # its OWN LRU entries down to its share before the
                # global bound runs — a hot aggressor can fill its
                # slice of the cache, never the whole pool.
                mine = [(k, b) for k, (_, b) in cache.items()
                        if k[0] == key[0]]
                mine_total = sum(b for _, b in mine)
                for k, b in mine:
                    if mine_total <= tenant_budget or k == key:
                        break
                    cache.pop(k, None)
                    mine_total -= b
                    evicted_n += 1
            total = sum(b for _, b in cache.values())
            while (len(cache) > self._result_cache_entries
                   or total > self._result_cache_bits) and len(cache) > 1:
                _, (_, evicted) = cache.popitem(last=False)
                total -= evicted
                evicted_n += 1
        if evicted_n:
            obs_metrics.RESULT_CACHE_EVICTIONS.inc(evicted_n)

    def tenant_cache_usage(self) -> dict:
        """Per-tenant cache residency for /debug/tenants and the
        ``pilosa_tenant_cache_bytes`` scrape refresh: result-residency
        bits (reported as bytes, bits/8) + cluster-cache entry
        counts, keyed by tenant (= index)."""
        out: dict[str, dict] = {}
        with self._bitmap_results_mu:
            for k, (_, bits) in self._bitmap_results.items():
                ent = out.setdefault(k[0], {"resultEntries": 0,
                                            "resultBits": 0,
                                            "clusterEntries": 0})
                ent["resultEntries"] += 1
                ent["resultBits"] += bits
        with self._cluster_cache_mu:
            for k in self._cluster_cache:
                ent = out.setdefault(k[0], {"resultEntries": 0,
                                            "resultBits": 0,
                                            "clusterEntries": 0})
                ent["clusterEntries"] += 1
        for ent in out.values():
            ent["bytes"] = ent["resultBits"] // 8
        return out

    def _execute_bitmap_call(self, index: str, c: Call, slices: list[int],
                             opt: ExecOptions) -> Bitmap:
        pnode = getattr(c, "_plan_node", None)
        if pnode is not None and pnode.short_circuit:
            obs_metrics.PLANNER_DECISIONS.labels("short_circuit_hit").inc()
            return Bitmap()
        compiled: list = []
        key = self._bitmap_result_key(index, c, slices, compiled)
        if key is not None:
            with self._bitmap_results_mu:
                hit = self._bitmap_results.get(key)
                if hit is not None:
                    self._bitmap_results.move_to_end(key)
            if hit is not None:
                obs_metrics.RESULT_CACHE_HITS.inc()
                obs_accounting.note_result_cache_hit(opt.ctx)
                return self._share_result(hit[0])
            obs_metrics.RESULT_CACHE_MISSES.inc()

        def map_fn(slice):
            return self._bitmap_call_slice(index, c, slice)

        def reduce_fn(prev, v):
            if prev is None:
                prev = Bitmap()
            prev.merge(v)
            return prev

        local_fn = self._bitmap_local_device_fn(
            index, c, opt, compiled=compiled[0] if compiled else None)
        bm = self._map_reduce(index, slices, c, opt, map_fn, reduce_fn,
                              local_fn=local_fn)
        if bm is None:
            bm = Bitmap()
        if c.name == "Bitmap":
            self._attach_bitmap_attrs(index, c, bm)
        if key is not None:
            self._result_cache_put(key, bm)
            return self._share_result(bm)
        return bm

    def _attach_bitmap_attrs(self, index: str, c: Call, bm: Bitmap) -> None:
        # executor.go:215-249: column attrs if the column label was used,
        # row attrs otherwise.
        idx = self.holder.index(index)
        if idx is None:
            return
        column_id, col_ok = c.uint_arg(idx.column_label)
        if col_ok:
            bm.attrs = idx.column_attr_store.attrs(column_id)
            return
        frame = idx.frame(c.args.get("frame") or DEFAULT_FRAME)
        if frame is not None:
            row_id, ok = c.uint_arg(frame.row_label)
            if ok:
                bm.attrs = frame.row_attr_store.attrs(row_id)

    def _bitmap_call_slice(self, index: str, c: Call, slice: int) -> Bitmap:
        # Plan consult (when the call was planned): proven-empty
        # subtrees return without touching storage, and CSE-marked
        # interior nodes go through the generation-token-keyed
        # subresult cache. The cache key embeds the (uid, generation)
        # token of every fragment the subtree reads, so a write
        # between queries changes the key — stale entries are never
        # served, they just age out of the LRU.
        node = getattr(c, "_plan_node", None)
        if node is not None:
            if node.short_circuit:
                return Bitmap()
            if node.cache_lookup and self.planner is not None:
                try:
                    key = self.planner.subresult_key(index, node, slice)
                except Exception:  # noqa: BLE001 - cache is best-effort
                    key = None
                if key is not None:
                    hit = self.planner.subresults.get(key)
                    if hit is not None:
                        return self._share_result(hit)
                    r = self._bitmap_slice_dispatch(index, c, slice)
                    if node.cache_store:
                        try:
                            self.planner.subresults.put(
                                key, self._share_result(r), r.count())
                        except Exception:  # noqa: BLE001
                            pass
                    return r
        return self._bitmap_slice_dispatch(index, c, slice)

    def _bitmap_slice_dispatch(self, index: str, c: Call,
                               slice: int) -> Bitmap:
        # executor.go:253-268
        if c.name == "Bitmap":
            return self._bitmap_slice(index, c, slice)
        if c.name == "Difference":
            return self._fold_slice(index, c, slice, "difference",
                                    require_children=True)
        if c.name == "Intersect":
            return self._fold_slice(index, c, slice, "intersect",
                                    require_children=True)
        if c.name == "Range":
            return self._range_slice(index, c, slice)
        if c.name == "Union":
            return self._fold_slice(index, c, slice, "union",
                                    require_children=False)
        raise PilosaError(f"unknown call: {c.name}")

    _HOST_FOLD_OPS = {"union": "or", "intersect": "and",
                      "difference": "andnot"}

    def _fold_slice(self, index: str, c: Call, slice: int, op: str,
                    require_children: bool) -> Bitmap:
        if require_children and not c.children:
            raise PilosaError(f"empty {c.name} query is currently"
                              " not supported")
        # Wide folds whose children are all plain Bitmap rows of one
        # (frame, view) collapse to ONE vectorized pass over the
        # fragment (fold_rows) instead of a roaring merge per child —
        # measured ~10× on the 1000-row config-2 shape. Narrow folds
        # and mixed/nested children keep the per-child merge, which
        # also owns all the error semantics.
        if len(c.children) >= self.mesh_min_leaves:
            plain = self._plain_fold_leaves(index, c)
            if plain is not None:
                frame_name, view, rids = plain
                frag = self.holder.fragment(index, frame_name, view,
                                            slice)
                if frag is None:
                    return Bitmap()
                if frag.fold_scan_pays(rids):
                    from .storage import roaring
                    out = Bitmap()
                    cols = frag.fold_rows(self._HOST_FOLD_OPS[op], rids)
                    if len(cols):
                        base = np.uint64(slice) * np.uint64(SLICE_WIDTH)
                        out.add_segment(
                            roaring.Bitmap.from_sorted(cols + base),
                            slice, writable=True)
                    return out
        out = Bitmap()
        for i, child in enumerate(c.children):
            bm = self._bitmap_call_slice(index, child, slice)
            out = bm if i == 0 else getattr(out, op)(bm)
        return out

    def _plain_fold_leaves(self, index: str, c: Call):
        """(frame, view, row ids) when every child is a plain Bitmap
        leaf of one (frame, view); None otherwise (the per-child path
        owns errors and mixed shapes)."""
        leaves: list[tuple] = []
        frame_view = None
        rids = []
        for child in c.children:
            expr = self._compile_device_expr(index, child, leaves)
            if expr is None or expr[0] != "leaf":
                return None
            frame_name, view, rid = leaves[expr[1]]
            if frame_view is None:
                frame_view = (frame_name, view)
            elif frame_view != (frame_name, view):
                return None
            rids.append(rid)
        if frame_view is None:
            return None
        return frame_view[0], frame_view[1], rids

    def _bitmap_slice(self, index: str, c: Call, slice: int) -> Bitmap:
        # executor.go:420-465: row id → standard view, column id → inverse.
        idx = self.holder.index(index)
        if idx is None:
            raise IndexNotFoundError(index)
        frame_name = c.args.get("frame") or DEFAULT_FRAME
        frame = idx.frame(frame_name)
        if frame is None:
            raise FrameNotFoundError(frame_name)
        row_id, row_ok = c.uint_arg(frame.row_label)
        col_id, col_ok = c.uint_arg(idx.column_label)
        if row_ok and col_ok:
            raise PilosaError(
                f"Bitmap() cannot specify both {frame.row_label} and"
                f" {idx.column_label} values")
        if not row_ok and not col_ok:
            raise PilosaError(
                f"Bitmap() must specify either {frame.row_label} or"
                f" {idx.column_label} values")
        view, id = VIEW_STANDARD, row_id
        if col_ok:
            view, id = VIEW_INVERSE, col_id
            if not frame.inverse_enabled:
                raise PilosaError("Bitmap() cannot retrieve columns unless"
                                  " inverse storage enabled")
        frag = self.holder.fragment(index, frame_name, view, slice)
        if frag is None:
            return Bitmap()
        return frag.row(id)

    def _range_views(self, index: str, c: Call, strict: bool):
        """Resolve a Range call to ``(frame_name, row_id, view_names)``
        — the minimal time-view cover (executor.go:490-546). The ONE
        parse both the host path and the device compiler use, so their
        semantics can't drift. ``strict`` raises the host path's errors;
        non-strict returns None (device compile declines, host owns the
        error). An empty view list means an empty result, not an error
        (frame without a time quantum, or an out-of-data window)."""
        frame_name = c.args.get("frame") or DEFAULT_FRAME
        frame = self.holder.frame(index, frame_name)
        if frame is None:
            if not strict:
                return None
            raise FrameNotFoundError(frame_name)
        row_id, ok = c.uint_arg(frame.row_label)
        if not ok:
            if not strict:
                return None
            raise PilosaError(
                f"Range() row field '{frame.row_label}' required")
        start = c.args.get("start")
        if start is None:
            if not strict:
                return None
            raise PilosaError("Range() start time required")
        end = c.args.get("end")
        if end is None:
            if not strict:
                return None
            raise PilosaError("Range() end time required")
        try:
            start_t = dt.datetime.strptime(start, TIME_FORMAT)
            end_t = dt.datetime.strptime(end, TIME_FORMAT)
        except (TypeError, ValueError):
            if not strict:
                return None
            raise PilosaError("cannot parse Range() time")
        q = frame.time_quantum()
        if not q:
            return frame_name, row_id, []
        return (frame_name, row_id,
                tq.views_by_time_range(VIEW_STANDARD, start_t, end_t, q))

    # -- BSI field ranges / aggregates (storage.bsi) -------------------------

    def _field_range_parse(self, index: str, c: Call, strict: bool):
        """Resolve a Range call carrying a ``field OP value`` condition
        to ``(frame_name, Field, Condition)``; None when it carries no
        condition or (non-strict) when the frame/field is missing —
        the strict form owns the host path's errors, like
        _range_views."""
        pair = c.condition_arg()
        if pair is None:
            return None
        field_name, cond = pair
        frame_name = c.args.get("frame") or DEFAULT_FRAME
        frame = self.holder.frame(index, frame_name)
        if frame is None:
            if not strict:
                return None
            raise FrameNotFoundError(frame_name)
        field = frame.field(field_name)
        if field is None:
            if not strict:
                return None
            raise PilosaError(f"field not found: {field_name}")
        return frame_name, field, cond

    @staticmethod
    def _bsi_plane_row(plane: int) -> int:
        """Circuit plane index (bsi.EXISTS_PLANE or value-bit i) → the
        field view's row id."""
        if plane == bsi.EXISTS_PLANE:
            return bsi.EXISTS_ROW
        return bsi.PLANE_ROW_OFFSET + plane

    def _field_range_slice(self, index: str, c: Call,
                           slice: int) -> Bitmap:
        """Host leg of Range(field OP value): the O(depth) bit-plane
        circuit over the fragment's rows in roaring algebra."""
        frame_name, field, cond = self._field_range_parse(index, c,
                                                          strict=True)
        frag = self.holder.fragment(index, frame_name, field.view_name,
                                    slice)
        if frag is None:
            return Bitmap()
        bm = bsi.range_bitmap(
            cond.op, cond.value, field.min, field.max,
            lambda plane: frag.row(self._bsi_plane_row(plane)))
        return bm if bm is not None else Bitmap()

    def _execute_field_aggregate(self, index: str, c: Call,
                                 slices: list[int],
                                 opt: ExecOptions) -> bsi.ValCount:
        """Sum / Min / Max over a BSI field, with an optional filter
        bitmap child: per-slice popcount-weighted plane folds, merged
        as (sum, count) addition / min-max combine across slices and
        nodes (the mapReduce partial-aggregate contract)."""
        name = c.name
        frame_name = c.args.get("frame") or DEFAULT_FRAME
        field_name = c.args.get("field")
        if not field_name or not isinstance(field_name, str):
            raise PilosaError(f"{name}() field required")
        frame = self.holder.frame(index, frame_name)
        if frame is None:
            raise FrameNotFoundError(frame_name)
        field = frame.field(field_name)
        if field is None:
            raise PilosaError(f"field not found: {field_name}")
        if len(c.children) > 1:
            raise PilosaError(
                f"{name}() only accepts a single bitmap input")
        child = c.children[0] if c.children else None
        want_min = name == "Min"

        def map_fn(slice):
            frag = self.holder.fragment(index, frame_name,
                                        field.view_name, slice)
            if frag is None:
                return bsi.ValCount(0, 0)
            filt = (self._bitmap_call_slice(index, child, slice)
                    if child is not None else None)

            def row(plane):
                return frag.row(self._bsi_plane_row(plane))
            if name == "Sum":
                return bsi.sum_count(field.min, field.max, row,
                                     filter=filt)
            return bsi.min_max(field.min, field.max, row, filter=filt,
                               want_min=want_min)

        def reduce_fn(prev, v):
            if v is None:
                return prev
            if prev is None:
                return v
            if name == "Sum":
                return bsi.combine_sum(prev, v)
            return bsi.combine_min_max(prev, v, want_min=want_min)

        device_fn = (self._sum_local_device_fn(index, frame_name,
                                               field, child, opt)
                     if name == "Sum" else None)

        def local_host_fn(batch_slices):
            # Whole-owned-slice pushdown (the TopN exact-partial leg
            # shape): the node leg answers ONE (sum,count) / min/max
            # partial computed in a single batched plane fold
            # (bsi.sum_count_many / min_max_many) instead of fanning
            # per-slice map tasks and reducing their ValCounts — on a
            # peer this is what the forwarded leg runs, so remote legs
            # are one partial each end to end.
            if device_fn is not None:
                r = device_fn(batch_slices)
                if r is not NotImplemented:
                    return r
            if (self.pod is not None and self.pod.is_coordinator
                    and not opt.pod_local):
                return NotImplemented  # pod fan-out is not a host leg
            legs = []
            for s in batch_slices:
                frag = self.holder.fragment(index, frame_name,
                                            field.view_name, s)
                if frag is None:
                    continue
                filt = (self._bitmap_call_slice(index, child, s)
                        if child is not None else None)
                legs.append(
                    (lambda plane, f=frag:
                     f.row(self._bsi_plane_row(plane)), filt))
            if name == "Sum":
                return bsi.sum_count_many(field.min, field.max, legs)
            return bsi.min_max_many(field.min, field.max, legs,
                                    want_min=want_min)

        result = self._map_reduce(index, slices, c, opt, map_fn,
                                  reduce_fn, local_fn=local_host_fn)
        return result or bsi.ValCount(0, 0)

    def _sum_local_device_fn(self, index: str, frame_name: str, field,
                             child: Optional[Call], opt: ExecOptions):
        """Device Sum: ONE mesh program computes every plane's
        popcount against the (compiled) filter — K = depth+1 fused
        counts through the existing batched-count machinery
        (mesh.count_exprs_sharded) over residency-cached plane slabs;
        the weighted fold Σ 2^i·count_i happens host-side in Python
        ints (no device overflow at any depth)."""
        if (not self.use_mesh or self.pod is not None
                or self._mesh_backoff_active()):
            return None
        leaves: list[tuple] = []
        filter_expr = None
        if child is not None:
            filter_expr = self._compile_device_expr(index, child, leaves)
            if filter_expr is None:
                return None
        exprs = []
        for plane in range(bsi.EXISTS_PLANE, field.bit_depth):
            leaves.append((frame_name, field.view_name,
                           self._bsi_plane_row(plane)))
            leaf = ("leaf", len(leaves) - 1)
            exprs.append(leaf if filter_expr is None
                         else ("and", leaf, filter_expr))
        exprs = tuple(exprs)

        def local_fn(slices: list[int]):
            if len(slices) < self.mesh_min_slices:
                return NotImplemented
            mesh = self._mesh_or_none()
            if mesh is None:
                return NotImplemented
            from .parallel import mesh as mesh_mod
            if len(slices) > mesh_mod.slice_chunk_bound(
                    mesh.shape[mesh_mod.AXIS_SLICES]):
                return NotImplemented
            shard, budget = self._count_budget(slices)
            if self._leaf_block_bytes(len(leaves), shard) > budget:
                return NotImplemented
            keys, found, cold = self._leaf_lookup(mesh, index, leaves,
                                                  slices)
            if not self._device_pays(mesh, len(leaves), len(slices),
                                     cold_rows=cold):
                return NotImplemented
            try:
                arrs = self._leaf_device_arrays(mesh, index, leaves,
                                                slices, (keys, found))
                counts = mesh_mod.count_exprs_sharded(mesh, exprs, arrs)
            except Exception as e:  # noqa: BLE001 - device trouble
                self._note_device_fallback("sum_exprs", e)
                return NotImplemented
            count = counts[0]
            total = field.min * count + sum(
                n << i for i, n in enumerate(counts[1:]))
            return bsi.ValCount(total, count)

        return local_fn

    def _execute_set_field_value(self, index: str, c: Call,
                                 opt: ExecOptions) -> bool:
        """SetFieldValue(frame=f, <col>=N, <field>=V): route to every
        replica owner of the column's slice, like SetBit
        (executor.go:664-691); the local apply is the frame's
        per-plane read-modify write."""
        frame_name = c.args.get("frame")
        if not frame_name:
            raise PilosaError("SetFieldValue() frame required")
        idx = self.holder.index(index)
        if idx is None:
            raise IndexNotFoundError(index)
        frame = idx.frame(frame_name)
        if frame is None:
            raise FrameNotFoundError(frame_name)
        col_id, ok = c.uint_arg(idx.column_label)
        if not ok:
            raise PilosaError(f"SetFieldValue() column field"
                              f" '{idx.column_label}' required")
        pairs = [(k, v) for k, v in c.args.items()
                 if k not in ("frame", idx.column_label)]
        if len(pairs) != 1:
            raise PilosaError(
                "SetFieldValue() requires exactly one field=value")
        field_name, value = pairs[0]
        if isinstance(value, bool) or not isinstance(value, int):
            raise PilosaError(
                f"SetFieldValue() value must be an integer: {value!r}")
        slice = col_id // SLICE_WIDTH
        ret = False
        for node in self.cluster.fragment_nodes(index, slice):
            if node.host == self.host:
                if (self.pod is not None and not opt.pod_local
                        and self.pod.owner_pid(slice) != self.pod.pid):
                    if self._pod_forward_field_value(index, c, slice):
                        ret = True
                    continue
                if frame.set_field_value(field_name, col_id, value):
                    ret = True
                continue
            if opt.remote:
                continue
            res = self._exec_remote(node, index, Query([c]), None, opt)
            if res and res[0]:
                ret = True
        return ret

    def _pod_forward_field_value(self, index: str, c: Call,
                                 slice: int) -> bool:
        """Forward a field-value write to the owning pod process (field
        views are column-sharded, so placement follows the column
        slice like standard views)."""
        pid = self.pod.owner_pid(slice)
        if self.client is None:
            raise SliceUnavailableError(
                f"no client to reach pod process {pid}")
        res = self.client.execute_query(
            Node(self.pod.peers[pid]), index, str(Query([c])), None,
            remote=True, pod_local=True)
        idx = self.holder.index(index)
        if idx is not None:
            idx.set_remote_max_slice(slice)
        return bool(res and res[0])

    def _range_slice(self, index: str, c: Call, slice: int) -> Bitmap:
        # executor.go:490-546: union the minimal time-view cover.
        if c.condition_arg() is not None:
            return self._field_range_slice(index, c, slice)
        frame_name, row_id, views = self._range_views(index, c,
                                                      strict=True)
        bm = Bitmap()
        for view in views:
            frag = self.holder.fragment(index, frame_name, view, slice)
            if frag is None:
                continue
            bm = bm.union(frag.row(row_id))
        return bm

    # -- Count (executor.go:568-597) -----------------------------------------

    def _execute_count(self, index: str, c: Call, slices: list[int],
                       opt: ExecOptions) -> int:
        if len(c.children) == 0:
            raise PilosaError("Count() requires an input bitmap")
        if len(c.children) > 1:
            raise PilosaError("Count() only accepts a single bitmap input")
        pnode = getattr(c, "_plan_node", None)
        if pnode is not None and pnode.short_circuit:
            obs_metrics.PLANNER_DECISIONS.labels("short_circuit_hit").inc()
            return 0

        # Count(Intersect(A, B)) host legs count WITHOUT materializing
        # the intersection — the reference's IntersectionCount shortcut
        # (bitmap.go:69-82, roaring.go:328-343); with the native
        # whole-bitmap count one crossing covers a slice.
        child = c.children[0]
        pairwise = (child.name == "Intersect"
                    and len(child.children) == 2
                    and all(gc.name == "Bitmap"
                            for gc in child.children))

        def map_fn(slice):
            if pairwise:
                a = self._bitmap_call_slice(index, child.children[0],
                                            slice)
                b = self._bitmap_call_slice(index, child.children[1],
                                            slice)
                return a.intersection_count(b)
            return self._bitmap_call_slice(index, c.children[0],
                                           slice).count()

        # Per-query routing note: a vetoed local_fn stamps the
        # predicted host cost here (it runs on a _map_reduce pool
        # worker, so a shared dict — not a threading.local — carries it
        # back); this site closes the loop by recording
        # (predicted, actual) into the cost model.
        note: dict = {}
        local_fn = self._count_local_device_fn(index, c.children[0],
                                               opt, note=note)

        def local_host_fn(batch_slices):
            # Time ONLY the local host batch (advisor r4: charging the
            # whole map-reduce wall — remote fan-out, reduce,
            # scheduling — to a prediction priced for the local leg's
            # bytes inflated host_scale on multi-node setups).
            r = (local_fn(batch_slices) if local_fn is not None
                 else NotImplemented)
            if r is not NotImplemented:
                return r
            if (self.pod is not None and self.pod.is_coordinator
                    and not opt.pod_local):
                return NotImplemented  # pod fan-out is not a host leg
            r, own_s, wall_s = self._timed_leg(
                lambda: self._mapper_local(
                    batch_slices, map_fn,
                    lambda prev, v: (prev or 0) + v))
            note["host_elapsed"] = note.get("host_elapsed", 0.0) + own_s
            note["host_wall"] = note.get("host_wall", 0.0) + wall_s
            return r

        result = self._map_reduce(index, slices, c, opt, map_fn,
                                  lambda prev, v: (prev or 0) + v,
                                  local_fn=local_host_fn)
        if "host_elapsed" in note:
            self._record_host_leg(note, note["host_elapsed"],
                                  note["host_wall"])
        return result or 0

    # -- device-batched Count (TPU fast path) --------------------------------

    def _device_batch_run(self, index: str, calls: list[Call], start: int,
                          slices: list[int], opt: ExecOptions):
        """(results, n_calls) for a maximal run of ≥2 consecutive
        device-lowerable calls starting at ``start`` — Count over any
        compilable bitmap tree (including BSI ``Range`` comparison
        circuits) and the exact-count TopN form (explicit ids + a
        compilable source) — fused into ONE device program over shared
        (deduplicated) leaf slabs, or None to fall back to per-call
        execution. A counts-only run dispatches the batched count
        program; a run carrying TopN blocks dispatches the fused-tree
        program (mesh.fused_tree_sharded): either way the whole tree
        pays one dispatch, one in-program reduction, one host fetch —
        not one crossing per call (the host-merge tax).

        Requires every touched slice to be locally owned (a pod counts
        as one node: its coordinator dispatches the batch as ONE pod
        work item): cluster map-reduce fans out per call, so batching
        a query with remote-only slices would bypass its remote legs —
        but a node owning a replica of everything (the common
        replica_n == nodes shape) answers the whole batch from local
        fragments and keeps the fused device fold. Count and TopN never
        take the inverse slice list (only Bitmap does), so every call
        in the run shares ``slices``. Pod runs fuse counts only (their
        per-kind programs serve TopN).
        """
        if not self.use_mesh or len(slices) < self.mesh_min_slices:
            return None
        if self.pod is not None and (not self.pod.is_coordinator
                                     or opt.pod_local):
            return None
        if self.pod is None and self._mesh_backoff_active():
            return None
        # Cheap necessary condition before any compile work: a run
        # needs ≥2 fusable calls, so a lone Count or TopN (the common
        # query shapes) must not pay a discarded device-expr
        # compilation (or the per-slice ownership walk below) here.
        if (start + 1 >= len(calls)
                or calls[start].name not in ("Count", "TopN")
                or calls[start + 1].name not in ("Count", "TopN")):
            return None
        if not self._owns_all_slices(index, slices):
            return None
        from .parallel import mesh as mesh_mod
        mesh = None
        if self.pod is None:
            mesh = self._mesh_or_none()
            if mesh is None or len(slices) > mesh_mod.slice_chunk_bound(
                    mesh.shape[mesh_mod.AXIS_SLICES]):
                return None
        shard, budget = self._count_budget(slices)
        leaves: list[tuple] = []
        leaf_ids: dict[tuple, int] = {}
        plan: list[tuple] = []       # ("count", expr) | ("topn", ...)
        topn_items: list[tuple] = []  # (expr, frame_name, ids)
        host_rows = 0  # per-call leaf rows: the host path's real bytes
        count_rows = 0  # those of the Counts: their host walk is priced
        rows_bytes = 0  # accumulated candidate-block bytes in the plan
        j = start

        def absorb(call_leaves: list[tuple], expr):
            """Intern a call's leaves into the shared slab set and
            remap its expr; returns the remapped expr."""
            remap = {}
            for li, leaf in enumerate(call_leaves):
                if leaf not in leaf_ids:
                    leaf_ids[leaf] = len(leaves)
                    leaves.append(leaf)
                remap[li] = leaf_ids[leaf]
            if all(k == v for k, v in remap.items()):
                return expr  # first call / no shared leaves
            return mesh_mod.remap_expr_leaves(expr, remap)

        while j < len(calls) and len(plan) < self._BATCH_MAX_COUNTS:
            c = calls[j]
            if c.name == "Count" and len(c.children) == 1:
                call_leaves: list[tuple] = []
                expr = self._compile_device_expr(index, c.children[0],
                                                 call_leaves)
                if expr is None:
                    break
                new = sum(1 for leaf in call_leaves
                          if leaf not in leaf_ids)
                if (self._leaf_block_bytes(len(leaves) + new, shard)
                        + rows_bytes > budget):
                    break  # fuse the prefix that fits; rest per call
                plan.append(("count", absorb(call_leaves, expr)))
                host_rows += len(call_leaves)
                count_rows += len(call_leaves)
                j += 1
                continue
            if c.name == "TopN" and self.pod is None:
                item = self._topn_fusable(index, c, slices, shard,
                                          budget - rows_bytes, leaves,
                                          leaf_ids)
                if item is None:
                    break
                expr, frame_name, ids, call_leaves = item
                plan.append(("topn", len(topn_items)))
                topn_items.append((absorb(call_leaves, expr),
                                   frame_name, ids))
                host_rows += len(ids) + len(call_leaves)
                # Every accepted candidate block stays live in the ONE
                # fused program — the budget must bound their SUM, not
                # each block alone (review finding: 16 × ~250 MB blocks
                # each passed a per-call check while the fused program
                # held ~4 GB of rows at once).
                rows_bytes += (len(slices) * len(ids)
                               * self._leaf_block_bytes(1, 1))
                j += 1
                continue
            break
        if j - start < 2:
            return None
        count_exprs = tuple(e for kind, e in plan if kind == "count")
        if self.pod is not None:
            if topn_items:
                return None  # unreachable: pod scan breaks at TopN
            try:
                counts = self.pod.count_exprs(index, list(count_exprs),
                                              leaves, slices)
            except Exception as e:  # noqa: BLE001 - per-call pod paths
                self._note_device_fallback("pod.count_exprs", e)
                return None
            return counts, j - start
        # One sync serves the whole tree; the host alternative re-walks
        # each call's leaves (and candidate rows), so its bytes are the
        # per-call sum — priced separately from the deduplicated device
        # block (costmodel host_bytes). A vetoed batch falls to
        # per-call gates that agree, landing everything on the host.
        from .parallel.residency import device_cache
        keys, found, cold = self._leaf_lookup(mesh, index, leaves,
                                              slices)
        rows_keys = []
        for expr_t, frame_name, ids in topn_items:
            rk = self._topn_rows_key(mesh, index, frame_name,
                                     tuple(ids), slices)
            rows_keys.append(rk)
            if not device_cache().contains(rk):
                cold += len(ids)
        device_rows = (len(leaves)
                       + sum(len(ids) for _, _, ids in topn_items))
        if not self._device_pays(mesh, device_rows, len(slices),
                                 cold_rows=cold, host_rows=host_rows,
                                 host_visits=count_rows * len(slices)):
            return None
        try:
            arrs = self._leaf_device_arrays(mesh, index, leaves, slices,
                                            (keys, found))
            if topn_items:
                from .parallel import residency
                rows_arrays = []
                for (expr_t, frame_name, ids), rk in zip(topn_items,
                                                         rows_keys):
                    rows_arrays.append(residency.candidate_block(
                        mesh, rk, self._key_frags(
                            index, frame_name, VIEW_STANDARD, slices,
                            rk), tuple(ids)))
                counts, topn_counts = mesh_mod.fused_tree_sharded(
                    mesh, count_exprs,
                    [(expr_t, len(ids))
                     for expr_t, _, ids in topn_items],
                    arrs, rows_arrays)
            else:
                counts = mesh_mod.count_exprs_sharded(
                    mesh, count_exprs, arrs)
                topn_counts = []
        except Exception as e:  # noqa: BLE001 - fall back per call
            self._note_device_fallback(
                "fused_tree" if topn_items else "count_exprs", e)
            return None
        results: list = []
        count_i = 0
        for kind, v in plan:
            if kind == "count":
                results.append(counts[count_i])
                count_i += 1
            else:
                _, _, ids = topn_items[v]
                results.append(pairs_sort(
                    [Pair(rid, cnt) for rid, cnt
                     in zip(ids, topn_counts[v]) if cnt > 0]))
        return results, j - start

    def _topn_fusable(self, index: str, c: Call, slices: list[int],
                      shard: int, budget: int, leaves: list[tuple],
                      leaf_ids: dict):
        """(expr, frame_name, ids, call_leaves) when this TopN call can
        join a fused device tree: the exact-count form (explicit ids +
        one compilable source child), unfiltered (threshold ≤ 1, no
        Tanimoto — the pruning forms need runtime scalars and keep
        their per-kind program), attribute filters applied host-side
        up front (row attrs are frame-global), candidate block within
        the resident byte bounds. None breaks the run (per-call paths
        own every other shape and all error semantics)."""
        (frame_name, _n, field, row_ids, min_threshold, filters,
         tanimoto) = self._topn_args(c)
        if (not row_ids or len(c.children) != 1 or tanimoto > 0
                or min_threshold > 1):
            return None
        call_leaves: list[tuple] = []
        expr = self._compile_device_expr(index, c.children[0],
                                         call_leaves)
        if expr is None:
            return None
        ids = self._attr_filtered_ids(index, frame_name, row_ids,
                                      field, filters)
        if ids is None or not ids:
            # No attr store, or nothing survives the filter: the
            # per-call path owns the (cheap) empty/fallback semantics.
            return None
        from .ops.packed import WORDS_PER_SLICE
        from .parallel import mesh as mesh_mod
        block_bytes = len(slices) * len(ids) * WORDS_PER_SLICE * 4
        new = sum(1 for leaf in call_leaves if leaf not in leaf_ids)
        if (block_bytes > self._topn_resident_bytes()
                or self._leaf_block_bytes(len(leaves) + new, shard)
                + block_bytes > budget):
            return None
        return expr, frame_name, ids, call_leaves

    _DEVICE_FOLD_OPS = {"Intersect": "and", "Union": "or",
                        "Difference": "andnot"}

    # Largest dense candidate block the TopN mesh path may materialize
    # host-side (slices × candidates × 128 KB); larger sets fall back.
    _TOPN_HOST_BLOCK_BYTES = 2 << 30
    # Max Count calls fused into one program: each distinct expr tuple
    # compiles its own XLA program, so unbounded runs would stall the
    # serving path in compilation (longer runs split into chunks).
    _BATCH_MAX_COUNTS = 16
    # HBM bound for one materializing fold: every leaf slab plus the
    # result are simultaneously live as the program's inputs/output.
    _MATERIALIZE_DEVICE_BYTES = 4 << 30

    @staticmethod
    def _topn_resident_bytes() -> int:
        """Largest TopN candidate block kept device-resident: half the
        residency budget, so that a block never evicts the leaf slabs
        it is counted against. (A fixed 256 MB bound was 8 candidate
        rows at 256 slices: TopN(n=10) at BASELINE config 4's width
        could never reach the device.)"""
        from .parallel.residency import device_cache
        return device_cache().budget_bytes // 2

    @staticmethod
    def _leaf_block_bytes(n_leaves: int, n_slices: int) -> int:
        from .ops.packed import WORDS_PER_SLICE
        return n_leaves * n_slices * WORDS_PER_SLICE * 4

    def _count_budget(self, slices: list[int]) -> tuple[int, int]:
        """(per-shard slice count, byte budget) for one Count program's
        leaf set. Resident single-host programs hold every slab live in
        HBM (_MATERIALIZE_DEVICE_BYTES); the streaming and pod paths
        chunk the device side but build the full numpy pack up-front,
        so the host-block bound applies to the (per-process) shard."""
        if self.pod is not None:
            return (self.pod.max_shard_slices(slices),
                    self._TOPN_HOST_BLOCK_BYTES)
        from .parallel import mesh as mesh_mod
        mesh = self._mesh
        n_dev = (mesh.shape[mesh_mod.AXIS_SLICES] if mesh is not None
                 else 1)
        if len(slices) <= mesh_mod.slice_chunk_bound(n_dev):
            return len(slices), self._MATERIALIZE_DEVICE_BYTES
        return len(slices), self._TOPN_HOST_BLOCK_BYTES

    def _compile_device_expr(self, index: str, c: Call, leaves: list):
        """``_compile_expr`` under the ``route`` stage: with
        _leaf_lookup, _device_pays and _leaf_device_arrays, everything
        that decides where a call runs and finds its operands."""
        with sched_context.stage("route"):
            return self._compile_expr(index, c, leaves)

    def _compile_expr(self, index: str, c: Call, leaves: list):
        """Compile a pure bitmap call tree into a mesh.count_expr tree.

        Supported: Bitmap leaves (standard or inverse) and Range (an
        or-fold over its minimal time-view cover — a leaf per view,
        executor.go:490-546) combined with Intersect/Union/Difference.
        Returns None when the tree contains anything else (malformed
        args, missing frames, no time quantum) — those run through the
        per-slice path, which owns the error semantics.
        """
        if c.name == "Range":
            if c.condition_arg() is not None:
                return self._compile_field_range_expr(index, c, leaves)
            parsed = self._range_views(index, c, strict=False)
            if parsed is None or not parsed[2]:
                return None  # malformed or empty cover: host path owns it
            frame_name, row_id, views = parsed
            expr = None
            for vn in views:
                leaves.append((frame_name, vn, row_id))
                part = ("leaf", len(leaves) - 1)
                expr = part if expr is None else ("or", expr, part)
            return expr
        if c.name == "Bitmap":
            idx = self.holder.index(index)
            if idx is None:
                return None
            frame = idx.frame(c.args.get("frame") or DEFAULT_FRAME)
            if frame is None:
                return None
            row_id, row_ok = c.uint_arg(frame.row_label)
            col_id, col_ok = c.uint_arg(idx.column_label)
            if row_ok == col_ok:
                return None
            view, id = (VIEW_STANDARD, row_id) if row_ok else \
                (VIEW_INVERSE, col_id)
            if view == VIEW_INVERSE and not frame.inverse_enabled:
                return None
            leaves.append((frame.name, view, id))
            return ("leaf", len(leaves) - 1)
        op = self._DEVICE_FOLD_OPS.get(c.name)
        if op is None or not c.children:
            return None
        parts = [self._compile_expr(index, ch, leaves)
                 for ch in c.children]
        if any(p is None for p in parts):
            return None
        expr = parts[0]
        for p in parts[1:]:  # n-ary folds left-to-right, like _fold_slice
            expr = (op, expr, p)
        return expr

    def _compile_field_range_expr(self, index: str, c: Call,
                                  leaves: list):
        """Compile Range(field OP value) into the comparison circuit
        over bit-plane leaves (storage.bsi.compare_expr — the SAME
        circuit the host path evaluates in roaring algebra), so field
        ranges compose with Count fusion, fold materialization, and
        plane-slab residency exactly like plain Bitmap leaves. Trivial
        clamps and provably-empty circuits decline (None): the host
        path computes those without a device round trip."""
        parsed = self._field_range_parse(index, c, strict=False)
        if parsed is None:
            return None
        frame_name, field, cond = parsed
        clamped = bsi.clamp(cond.op, cond.value, field.min, field.max)
        if clamped == "none":
            return None
        leaf_ids: dict[tuple, int] = {}

        def leaf(plane: int):
            key = (frame_name, field.view_name,
                   self._bsi_plane_row(plane))
            if key not in leaf_ids:
                leaves.append(key)
                leaf_ids[key] = len(leaves) - 1
            return ("leaf", leaf_ids[key])

        if clamped == "all":
            return leaf(bsi.EXISTS_PLANE)
        cop, upred = clamped
        return bsi.compare_expr(cop, upred, field.bit_depth, leaf)

    def _bitmap_local_device_fn(self, index: str, c: Call,
                                opt: ExecOptions, compiled=None):
        """Materializing Union/Intersect/Difference on device for WIDE
        fan-outs (BASELINE config 2: Union over 1 K rows): fold the
        packed leaf slabs in one sharded program (the leaf axis reduces
        associatively on device), fetch the dense result words, and
        repack to roaring segments — replacing leaf-count many
        container-walking merges (roaring.go:1270-1558) with one HBM
        pass. Narrow calls keep the host path: below ~mesh_min_leaves
        rows the roaring merges beat the device sync + repack."""
        if (not self.use_mesh or self.pod is not None
                or self._mesh_backoff_active()):
            return None  # pod host legs own pod materialization
        pnode = getattr(c, "_plan_node", None)
        if pnode is not None and pnode.placement == "host":
            self.cost_vetoes += 1   # the model's prices, the planner's hint
            return None  # planner priced the subtree cheaper on host
        if c.name == "Range" and c.condition_arg() is not None:
            return self._field_range_local_device_fn(index, c)
        if c.name not in ("Union", "Intersect", "Difference"):
            return None
        if compiled is not None:
            expr, leaves = compiled
        else:
            leaves = []
            expr = self._compile_device_expr(index, c, leaves)
        if expr is None or len(leaves) < self.mesh_min_leaves:
            return None

        def local_fn(slices: list[int]):
            from .ops import packed
            slab = len(slices) * packed.WORDS_PER_SLICE * 4
            # Peak HOST allocation is the dense result block plus one
            # transient leaf slab (slabs pack one at a time before the
            # device_put); all leaf slabs plus the result are live in
            # HBM together as inputs/output of the one fold program.
            if (2 * slab > self._TOPN_HOST_BLOCK_BYTES
                    or (len(leaves) + 1) * slab
                    > self._MATERIALIZE_DEVICE_BYTES):
                return NotImplemented
            mesh = self._mesh_or_none()
            if mesh is None:
                return NotImplemented
            from .parallel import mesh as mesh_mod
            try:
                arrs = self._leaf_device_arrays(mesh, index, leaves,
                                                slices)
                words = mesh_mod.materialize_expr_sharded(mesh, expr,
                                                          arrs)
            except Exception as e:  # noqa: BLE001 - device trouble
                self._note_device_fallback("materialize", e)
                return NotImplemented
            out = Bitmap()
            for si, slice in enumerate(slices):
                w = words[si]
                if not w.any():
                    continue
                data = packed.unpack_to_bitmap(
                    w, base_word=slice * (packed.WORDS_PER_SLICE))
                out.add_segment(data, slice, writable=True)
            return out

        return local_fn

    def _field_range_local_device_fn(self, index: str, c: Call):
        """Materializing device leg of Range(field OP value): the whole
        comparison circuit over stacked bit-plane slabs runs as ONE
        XLA program (parallel.mesh.bsi_range_sharded — exists row plus
        depth value planes, sharded over the slice axis), the dense
        matched words fetch once, and the host repacks to roaring —
        replacing O(depth) per-slice roaring circuit passes with one
        HBM pass. Trivial clamps ("all"/"none") stay host-side."""
        parsed = self._field_range_parse(index, c, strict=False)
        if parsed is None:
            return None
        frame_name, field, cond = parsed
        clamped = bsi.clamp(cond.op, cond.value, field.min, field.max)
        if clamped in ("none", "all"):
            return None
        cop, upred = clamped
        depth = field.bit_depth
        leaves = [(frame_name, field.view_name,
                   self._bsi_plane_row(p))
                  for p in range(bsi.EXISTS_PLANE, depth)]

        def local_fn(slices: list[int]):
            if len(slices) < self.mesh_min_slices:
                return NotImplemented
            from .ops import packed
            slab = len(slices) * packed.WORDS_PER_SLICE * 4
            if (2 * slab > self._TOPN_HOST_BLOCK_BYTES
                    or (len(leaves) + 1) * slab
                    > self._MATERIALIZE_DEVICE_BYTES):
                return NotImplemented
            mesh = self._mesh_or_none()
            if mesh is None:
                return NotImplemented
            from .parallel import mesh as mesh_mod
            try:
                arrs = self._leaf_device_arrays(mesh, index, leaves,
                                                slices)
                words = mesh_mod.bsi_range_sharded(mesh, cop, upred,
                                                   depth, arrs)
            except Exception as e:  # noqa: BLE001 - device trouble
                self._note_device_fallback("bsi_range", e)
                return NotImplemented
            out = Bitmap()
            for si, slice in enumerate(slices):
                w = words[si]
                if not w.any():
                    continue
                data = packed.unpack_to_bitmap(
                    w, base_word=slice * packed.WORDS_PER_SLICE)
                out.add_segment(data, slice, writable=True)
            return out

        return local_fn

    def _count_local_device_fn(self, index: str, child: Call,
                               opt: ExecOptions, note: dict | None = None):
        """Batched local-leg Count: all slices in ONE mesh program.

        Returns a ``local_fn(slices) -> int`` for _map_reduce, or None
        when the expression can't run on device. Leaf rows are packed
        host-side into [n_leaves, n_slices, words] and the whole
        expression + popcount + sum runs as a single psum-reduced SPMD
        call (parallel.mesh.count_expr) — the mesh form of the per-slice
        count map (executor.go:568-597). On a pod coordinator the call
        becomes a pod-wide collective (parallel.pod.Pod.count_expr);
        pod workers and podLocal legs use the host path.
        """
        if not self.use_mesh:
            return None
        if self.pod is None and self._mesh_backoff_active():
            return None
        pnode = getattr(child, "_plan_node", None)
        if pnode is not None and pnode.placement == "host":
            # The planner priced the subtree cheaper on the host, with
            # the executor's constants: a veto like _device_pays', so
            # counted with them, and its host leg is held against the
            # planner's prediction (the hint is then corrected by the
            # same loop, not trusted for ever).
            self.cost_vetoes += 1
            if note is not None and pnode.est_cost_s:
                note["host_pred"] = pnode.est_cost_s
            return None
        leaves: list[tuple] = []
        expr = self._compile_device_expr(index, child, leaves)
        if expr is None:
            return None
        if self.pod is not None:
            if not self.pod.is_coordinator or opt.pod_local:
                return None  # plain local path on pod-internal legs

            def pod_fn(slices: list[int]):
                shard, budget = self._count_budget(slices)
                if (len(slices) < self.mesh_min_slices
                        or self._leaf_block_bytes(len(leaves), shard)
                        > budget):
                    return NotImplemented  # pod host legs win when small
                try:
                    return self.pod.count_expr(index, expr, leaves, slices)
                except Exception as e:  # noqa: BLE001 - pod host fan-out
                    self._note_device_fallback("pod.count_expr", e)
                    return NotImplemented  # correct via _pod_host_mapper
            return pod_fn

        def local_fn(slices: list[int]):
            if len(slices) < self.mesh_min_slices:
                return NotImplemented  # host path wins below the sync cost
            mesh = self._mesh_or_none()  # backend init only past threshold
            if mesh is None:
                return NotImplemented
            from .parallel import mesh as mesh_mod
            # Above the chunk bound the block is re-packed every query.
            streaming = len(slices) > mesh_mod.slice_chunk_bound(
                mesh.shape[mesh_mod.AXIS_SLICES])
            # One ``route`` entry for the whole decision (the callees'
            # own route stages collapse into it).
            with sched_context.stage("route"):
                keys, found, cold = self._leaf_lookup(mesh, index,
                                                      leaves, slices)
                if not self._device_pays(
                        mesh, len(leaves), len(slices), cold_rows=cold,
                        note=note, streaming=streaming,
                        host_visits=len(leaves) * len(slices)):
                    return NotImplemented  # calibrated: host faster
                shard, budget = self._count_budget(slices)
                if self._leaf_block_bytes(len(leaves), shard) > budget:
                    return NotImplemented  # oversized leaf set: host
            try:
                def run():
                    if not streaming:
                        # Residency fast path: leaf slabs stay device-
                        # resident across queries (budgeted HBM cache);
                        # the cold ones are filled here, once.
                        arrs = self._leaf_device_arrays(
                            mesh, index, leaves, slices, (keys, found))
                        return mesh_mod.count_expr_sharded(mesh, expr,
                                                           arrs)
                    block = self._pack_leaf_block(index, leaves, slices)
                    return mesh_mod.count_expr(mesh, expr, block)
                return self._timed_device_leg(run, len(leaves),
                                              len(slices),
                                              cold_rows=cold,
                                              streaming=streaming)
            except Exception as e:  # noqa: BLE001 - device trouble ≠ node down
                self._note_device_fallback("count_expr", e)
                return NotImplemented

        return local_fn

    def calibrate(self, mesh) -> bool:
        """Measure this process's routing constants on first device
        use (sched.warmup calls it at start-up, before the first
        query); False when the cost model is off."""
        if not self._cost_model_enabled:
            return False
        if self.cost_model is None:
            from .parallel import costmodel
            try:
                self.cost_model = costmodel.get_model(
                    mesh, _measure_host_visit_s,
                    margin=self._cost_margin)
            except Exception:  # noqa: BLE001 - never fail a query on this
                self._cost_model_enabled = False
                return False
            # Share the measured constants with the planner so its
            # host/device placement prices match the executor's veto.
            if self.planner is not None:
                self.planner.calibration = self.cost_model.cal
        return True

    def _device_pays(self, mesh, n_rows: int, n_slices: int,
                     cold_rows: int = 0, note: dict | None = None,
                     streaming: bool = False,
                     host_rows: int | None = None,
                     host_visits: int = 0) -> bool:
        """Calibrated routing veto: False when the host path clearly
        wins for a block of ``n_rows × n_slices`` packed rows on this
        hardware. ``cold_rows`` of those are not device-resident. A
        slab the residency cache keeps is filled once, by the leg that
        finds it cold, and read many times, where the host path is
        paid on every read: its fill is no part of a read's price, and
        a read with cold leaves is placed where the same read with
        resident leaves is. (Priced against the read, the fill vetoed
        the leg, the host answer filled nothing, and the next read of
        the row was vetoed again.) Pack + upload stay in the price
        only where they are paid on every query: a ``streaming`` leg,
        and a slab larger than the whole residency budget, which is
        never kept. ``host_rows`` (fused multi-op trees) is the
        PER-CALL leaf-row sum the host alternative would walk — the
        device block deduplicates shared leaves and pays ONE crossing
        for the whole tree, so pricing the host on the deduplicated
        bytes over-charged the mesh leg exactly when fusion helps
        most. ``host_visits``: the leaves × slices the Counts of the
        host alternative would walk, whose per-fragment cost the
        calibration timed (Calibration.host_visit_s); 0 for what else
        it would run (TopN's ranked cache, the BSI aggregates), which
        no probe measures: the byte term alone prices that."""
        with sched_context.stage("route"):
            return self._device_pays_priced(
                mesh, n_rows, n_slices, cold_rows, note, streaming,
                host_rows, host_visits)

    def _device_pays_priced(self, mesh, n_rows, n_slices, cold_rows,
                            note, streaming, host_rows,
                            host_visits) -> bool:
        if not self.calibrate(mesh):
            return True
        from .ops.packed import WORDS_PER_SLICE
        row_bytes = n_slices * WORDS_PER_SLICE * 4
        host_bytes = (host_rows if host_rows is not None
                      else n_rows) * row_bytes
        if cold_rows and not streaming:
            from .parallel import residency
            if residency.slab_is_kept(mesh, n_slices):
                cold_rows = 0   # filled once: not this read's to pay
        model = self.cost_model
        pays = model.device_pays(
            n_rows * row_bytes, cold_bytes=cold_rows * row_bytes,
            streaming=streaming, host_bytes=host_bytes,
            host_visits=host_visits)
        if not pays:
            self.cost_vetoes += 1
            if note is not None:
                # Stamp the host leg's prediction for this query; the
                # _map_reduce caller records actual-vs-predicted.
                note["host_pred"] = model.predict(
                    "host", host_bytes, host_visits=host_visits)
        return pays

    def _timed_device_leg(self, fn, n_rows: int, n_slices: int,
                          cold_rows: int = 0, streaming: bool = False):
        """Run a device leg and feed (predicted, actual) back into the
        cost model's drift loop (no-op when the model is off).
        Streaming legs (block re-packed every query) record under
        their own leg — the prediction prices the packing via
        pack_bps, so they participate in drift correction instead of
        being excluded. A resident leg that has a slab to fill is not
        a sample: its fill is no part of its price (_device_pays), and
        ``device_scale`` multiplies what a read of resident slabs
        costs — a pack beside seven other packing threads folded it
        ×70, and the resident reads after it were vetoed (chip run,
        PR 32). It still counts among the legs in flight."""
        model = self.cost_model
        if model is None:
            return fn()
        out, own_s, wall_s = self._timed_leg(fn)
        if streaming or not cold_rows:
            from .ops.packed import WORDS_PER_SLICE
            leg = "device_stream" if streaming else "device"
            row_bytes = n_slices * WORDS_PER_SLICE * 4
            model.record(leg, model.predict(leg, n_rows * row_bytes,
                                            cold_rows * row_bytes),
                         own_s, wall_s)
        return out

    def _timed_leg(self, fn) -> tuple:
        """(fn(), the least seconds of it that were its own, its wall).
        Legs that run at once share one interpreter and one device, so
        the wall of a leg among N is up to N times what it costs alone,
        whichever leg it is; the constants it is held against were
        measured alone. What it cost alone lies between its wall over
        the timed legs in flight (the mean of the count at its start
        and at its end) and its wall; the drift loop is given both
        ends (CostModel.record)."""
        token = object()
        legs = self._timed_legs
        legs.add(token)
        n0 = len(legs)
        t0 = time.perf_counter()
        try:
            out = fn()
        finally:
            elapsed = time.perf_counter() - t0
            n1 = len(legs)
            legs.discard(token)
        return out, elapsed / ((n0 + n1) / 2), elapsed

    def _record_host_leg(self, note: dict, own_s: float,
                         wall_s: float) -> None:
        """Close the loop for a query the model routed to the host."""
        pred = note.get("host_pred")
        if pred is not None and self.cost_model is not None:
            self.cost_model.record("host", pred, own_s, wall_s)

    @staticmethod
    def _slices_key(slices) -> tuple:
        """The slice set as one part of a residency key: the route
        record's interned ``("r", first, n)`` when ``slices`` is a
        record's own list, the same triple for any other contiguous
        ascending run (equal sets key equally, however they arrived),
        else the tuple itself."""
        route = getattr(slices, "route", None)
        if route is not None:
            return route["skey"]
        n = len(slices)
        if n and slices[-1] - slices[0] == n - 1 and (
                n < 3 or list(slices) == list(
                    range(slices[0], slices[0] + n))):
            return ("r", slices[0], n)
        return tuple(slices)

    def _leaf_frags(self, index: str, frame: str, view: str,
                    slices) -> list:
        """One fragment a slice (None = absent), by the per-slice walk:
        what a route record keeps a view, and its oracle."""
        v = self.holder.view(index, frame, view)
        if v is None:
            return [None] * len(slices)
        frag = v.fragment
        return [frag(s) for s in slices]

    def _view_token(self, index: str, frame: str, view: str, slices,
                    walked: Optional[list] = None) -> tuple[int, int]:
        """The (uid, generation) of a leaf's view — (0, 0) while the
        view does not exist — read BEFORE anything is resolved under
        it. Where ``slices`` is a route record's list the record's
        entry for the view (its fragment list under that token) is
        confirmed, or resolved again by the walk; ``walked`` then
        gains one entry: True where an entry's token had moved."""
        v = self.holder.view(index, frame, view)
        if v is None:
            return (0, 0)
        return self._route_view(v, index, frame, view, slices, walked)

    def _route_view(self, v, index: str, frame: str, view: str, slices,
                    walked: Optional[list]) -> tuple[int, int]:
        """``_view_token`` of the view ``v`` itself."""
        tok = (v.uid, v.generation)
        route = getattr(slices, "route", None)
        if route is None:
            return tok
        ent = route["views"].get((frame, view))
        if ent is not None and ent[0] == tok:
            return tok
        if walked is not None:
            walked.append(ent is not None)
        route["views"][(frame, view)] = (
            tok, self._leaf_frags(index, frame, view, slices))
        return tok

    def _leaf_cache_key(self, mesh, index: str, leaf: tuple, slices,
                        walked: Optional[list] = None) -> tuple:
        """Residency key of one leaf slab, O(1) in the slice count: the
        slice set by name, the view's uid and the ROW's token
        (``View.row_token``), read before anything is resolved: a write
        to another row leaves the key, and the slab, as they are. The
        view's token is confirmed against the route record all the same
        (``routeMemo``)."""
        from .parallel import mesh as mesh_mod
        frame, view, row_id = leaf
        v = self.holder.view(index, frame, view)
        if v is None:
            uid = epoch = row_epoch = 0
        else:
            uid, epoch, row_epoch = (v.uid, v.epoch,
                                     v.row_epochs.get(row_id, 0))
            self._route_view(v, index, frame, view, slices, walked)
        return ("leaf", id(self.holder), index, frame, view, row_id,
                self._slices_key(slices), uid, epoch, row_epoch,
                mesh.shape[mesh_mod.AXIS_SLICES])

    def _leaf_lookup(self, mesh, index: str, leaves: list[tuple],
                     slices) -> tuple[list, list, int]:
        """(keys, resident arrays, cold count) of an upcoming dispatch:
        each leaf's key built once and looked up once, in one hold of
        the residency cache's lock and without touching its LRU order —
        a leaf that is absent (None) counts as cold for _device_pays
        and is built only if the device leg is taken
        (_leaf_device_arrays). This is also where a read's route is
        counted (``routeMemo``): a hit when ``slices`` is a route
        record's list and every leaf's view was reused from it."""
        from .parallel.residency import device_cache
        with sched_context.stage("route"):
            walked: list = []
            keys = [self._leaf_cache_key(mesh, index, leaf, slices, walked)
                    for leaf in leaves]
            found = device_cache().lookup(keys)
            routed = getattr(slices, "route", None) is not None
            self._route_note("hits" if routed and not walked
                             else "misses", invalidated=sum(walked))
            return keys, found, found.count(None)

    def _leaf_device_arrays(self, mesh, index: str, leaves: list[tuple],
                            slices, looked=None) -> list:
        """Device-resident [bucket(n_slices), words] slab of every leaf
        row, held in the budgeted HBM cache (parallel.residency.
        leaf_slab — bucket-padded so the program catalogue's compiled
        shapes stay stable as slice count grows). ``looked`` is the
        (keys, found) of the leg's own _leaf_lookup, so that a leaf is
        looked up once; what it found is reported as hits now that the
        leg is taken, and only the absent slabs resolve fragments.

        The key embeds the view's uid and the leaf row's token, so a
        write to the row or a reopen of the view drops the slab (and a
        fill that raced it keeps nothing) — repeated Count/TopN over
        rows nobody writes re-use the upload instead of re-packing +
        re-transferring per query. A miss resolves its fragments by the
        walk, after the key's token was read: every fragment the token
        vouches for is in it."""
        from .parallel import residency
        with sched_context.stage("route"):  # a miss nests pack, upload
            keys, found = (looked if looked is not None else
                           self._leaf_lookup(mesh, index, leaves,
                                             slices)[:2])
            residency.device_cache().touch(
                [k for k, a in zip(keys, found) if a is not None])
            arrs = list(found)
            for i, arr in enumerate(arrs):
                if arr is None:
                    frame, view, row_id = leaves[i]
                    key = keys[i]
                    v = self.holder.view(index, frame, view)
                    # a view made anew since the key: build, keep none
                    owner = (None if v is None else (
                        v, row_id,
                        key[-3:-1] if v.uid == key[-4] else None))
                    arrs[i] = residency.leaf_slab(
                        mesh, key,
                        lambda f=frame, w=view: self._leaf_frags(
                            index, f, w, slices), row_id, owner)
            return arrs

    def _key_frags(self, index: str, frame: str, view: str, slices,
                   key: tuple):
        """The lazy fragment list of a candidate block under residency
        ``key`` (only a miss resolves it): the route record's, where it
        holds one resolved under the key's view token (a list resolved
        after the token was read shows every fragment the token vouches
        for), else the walk."""
        def frags():
            route = getattr(slices, "route", None)
            if route is not None:
                ent = route["views"].get((frame, view))
                if ent is not None and ent[0] == key[-3:-1]:
                    return ent[1]
            return self._leaf_frags(index, frame, view, slices)
        return frags

    def _pack_leaf_block(self, index: str, leaves: list[tuple],
                         slices: list[int]) -> np.ndarray:
        """[n_leaves, n_slices, words] block of packed leaf rows; absent
        fragments stay zero (the identity for every count reduce)."""
        from .ops.packed import WORDS_PER_SLICE
        block = np.zeros((len(leaves), len(slices), WORDS_PER_SLICE),
                         dtype=np.uint32)
        for li, (frame, view, row_id) in enumerate(leaves):
            for si, slice in enumerate(slices):
                frag = self.holder.fragment(index, frame, view, slice)
                if frag is not None:
                    frag.pack_row(row_id, out=block[li, si])
        return block

    # -- TopN (executor.go:271-396) ------------------------------------------

    def _execute_top_n(self, index: str, c: Call, slices: list[int],
                       opt: ExecOptions) -> list[Pair]:
        row_ids, _ = c.uint_slice_arg("ids")
        n, _ = c.uint_arg("n")

        if opt.remote and not row_ids and c.args.get("pushdown"):
            # Pushdown leg (ROADMAP item 3): the coordinator asked
            # this node to run the WHOLE TopN algorithm over its own
            # slices — single-pass when the rank caches allow, exact
            # local two-phase otherwise — and return untrimmed exact
            # partials for the two-phase merge.
            return self._topn_exact_partial(index, c, slices, opt)

        with_src = not opt.remote and not row_ids and len(c.children) == 1
        if with_src:
            dev = self._topn_src_device(index, c, slices)
            obs_accounting.note_topn_src("device" if dev is not None
                                         else "host")
            if dev is not None:
                self.topn_src["srcDevice"] += 1
                return dev
            self.topn_src["srcHost"] += 1

        fast = self._topn_host_single_pass(index, c, slices, opt)
        if fast is not None:
            return fast

        if not opt.remote and not row_ids:
            dist = self._topn_distributed(index, c, slices, opt, n)
            if dist is not None:
                return dist

        pairs = self._top_n_slices(index, c, slices, opt)
        if with_src:
            self.topn_src["candidates"] += len(pairs)
        # Only the originating node refetches exact counts for candidates.
        if not pairs or row_ids or opt.remote:
            return pairs
        other = c.clone()
        other.args["ids"] = sorted({p.id for p in pairs})
        dev = self._topn_device_topk(index, other, slices, n)
        if dev is not None:
            return dev
        trimmed = self._top_n_slices(index, other, slices, opt)
        if n and n < len(trimmed):
            trimmed = trimmed[:n]
        return trimmed

    def _topn_src_device(self, index: str, c: Call,
                         slices: list[int]) -> Optional[list[Pair]]:
        """The with-source TopN — ``TopN(<bitmap>, frame=f, n=k)`` — with
        both phases (executor.go:271-396) read off ONE device program,
        or None for today's paths. The frame's standard view is held
        on the device sized to its bits (residency.frame_positions);
        the program gives every row's source-intersection count in
        every slice (mesh.topn_src_counts). Phase 1 is each slice's
        walk of its rank cache fed those counts in place of the host
        count map (Fragment.top_src: the superset rule, the threshold,
        the cache's trim and tie order as ``Fragment.top`` has them);
        phase 2's exact count of a candidate is the sum of the same
        counts over the slices: no second dispatch, no candidate
        block. The plain form only (n > 0, threshold ≤ 1, no Tanimoto,
        no attribute filter) on a node that owns every slice, off a
        pod; a frame form larger than half the residency budget leaves
        the read to today's paths."""
        if (not self.use_mesh or self.pod is not None
                or self._mesh_backoff_active()):
            return None
        (frame_name, n, field, _ids, min_threshold, filters,
         tanimoto) = self._topn_args(c)
        if (not n or (field and filters) or tanimoto > 0
                or min_threshold > 1
                or len(slices) < self.mesh_min_slices
                or not self._owns_all_slices(index, slices)):
            return None
        view = self.holder.view(index, frame_name, VIEW_STANDARD)
        if view is None:
            return None
        mesh = self._mesh_or_none()
        if mesh is None:
            return None
        from .ops.packed import WORDS_PER_SLICE
        from .parallel import mesh as mesh_mod
        from .parallel import residency
        if len(slices) > mesh_mod.slice_chunk_bound(
                mesh.shape[mesh_mod.AXIS_SLICES]):
            return None
        leaves: list[tuple] = []
        expr = self._compile_device_expr(index, c.children[0], leaves)
        if expr is None:
            return None
        with sched_context.stage("route"):
            token = view.frame_token()      # before anything is resolved
            key = ("topnframe", id(self.holder), index, frame_name,
                   self._slices_key(slices), view.uid, token,
                   mesh.shape[mesh_mod.AXIS_SLICES])
            frags = self._leaf_frags(index, frame_name, VIEW_STANDARD,
                                     slices)
            if any(f is not None and not hasattr(f.cache, "top_arrays")
                   for f in frags):
                return None
            slice_bits = [0 if f is None else f._cached_total_bits()
                          for f in frags]
            bits = sum(slice_bits)
            n_rows = 1 + max((f.max_row_id() for f in frags
                              if f is not None), default=0)
            form_bytes = residency.frame_form_bytes(mesh, slice_bits,
                                                    n_rows)
            if form_bytes > self._topn_resident_bytes():
                return None
            keys, found, _cold = self._leaf_lookup(mesh, index, leaves,
                                                   slices)
            row_bytes = len(slices) * WORDS_PER_SLICE * 4
            # The host walk reads every set bit as a u64 position per
            # source (Fragment._host_src_count_map); the device reads
            # the form once. Both are kept once built: no fill priced.
            if not self._device_pays(
                    mesh, -(-form_bytes // row_bytes) + len(leaves),
                    len(slices), host_rows=-(-8 * bits // row_bytes)):
                return None
        try:
            arrs = self._leaf_device_arrays(mesh, index, leaves, slices,
                                            (keys, found))
            form = residency.frame_positions(
                mesh, key, frags, n_rows,
                owner=(view, None, token))
            with sched_context.span(
                    "topn_src_counts", rows=form[0].shape[1] - 1,
                    slices=len(slices), positions=bits,
                    bytes=form[0].nbytes + form[1].nbytes):
                counts = mesh_mod.topn_src_counts(mesh, expr, form, arrs)
        except Exception as e:  # noqa: BLE001 - device trouble ≠ node down
            self._note_device_fallback("topn_src_counts", e)
            return None
        with sched_context.stage("topn_replay"):
            parts = []
            for si, frag in enumerate(frags):
                if frag is not None:
                    ids, _ = frag.top_src(counts[si], n, min_threshold)
                    parts.append(ids)
            cand = (np.unique(np.concatenate(parts)) if parts
                    else np.empty(0, dtype=np.int64))
            self.topn_src["candidates"] += len(cand)
            sums = counts[:len(slices), cand].sum(axis=0, dtype=np.int64)
            order = np.lexsort((cand, -sums))[:n]
            return [Pair(i, cnt) for i, cnt in
                    zip(cand[order].tolist(), sums[order].tolist())
                    if cnt > 0]

    def _topn_device_topk(self, index: str, c: Call,
                          slices: list[int],
                          n: int) -> Optional[list[Pair]]:
        """The sourceless TopN exact-count refetch as ONE in-program
        device top-k (mesh.topn_topk_sharded): the candidate union from
        phase 1 uploads as a resident block, per-candidate counts
        reduce in-program, and the top-k selection ALSO happens in the
        program, so the host fetch is O(n) instead of O(candidates).
        Plain form only — thresholds > 1, Tanimoto, attribute filters,
        and pod legs keep the host path, which owns those semantics.
        Counts are fresh popcounts, identical to the host refetch's
        row_count recounts; ordering matches pairs_sort (count desc,
        id asc) by the program's tie-break. None = fall back."""
        if not self.use_mesh or self.pod is not None \
                or self._mesh_backoff_active():
            return None
        (frame_name, _n, field, row_ids, min_threshold, filters,
         tanimoto) = self._topn_args(c)
        if (len(c.children) > 0 or (field and filters) or tanimoto > 0
                or min_threshold > 1 or not row_ids
                or len(slices) < self.mesh_min_slices
                or not self._owns_all_slices(index, slices)):
            return None
        mesh = self._mesh_or_none()
        if mesh is None:
            return None
        from .ops.packed import WORDS_PER_SLICE
        from .parallel import mesh as mesh_mod
        from .parallel import residency
        ids = list(row_ids)
        block_bytes = len(slices) * len(ids) * WORDS_PER_SLICE * 4
        if (block_bytes > self._TOPN_HOST_BLOCK_BYTES
                or block_bytes > self._topn_resident_bytes()
                or len(slices) > mesh_mod.slice_chunk_bound(
                    mesh.shape[mesh_mod.AXIS_SLICES])):
            return None
        rows_key = self._topn_rows_key(mesh, index, frame_name,
                                       tuple(ids), slices)
        cold = (0 if residency.device_cache().contains(rows_key)
                else len(ids))
        if not self._device_pays(mesh, len(ids), len(slices),
                                 cold_rows=cold, streaming=False):
            return None
        k = min(n, len(ids)) if n else len(ids)
        try:
            def run():
                rows_arr = residency.candidate_block(
                    mesh, rows_key,
                    self._key_frags(index, frame_name, VIEW_STANDARD,
                                    slices, rows_key), tuple(ids))
                return mesh_mod.topn_topk_sharded(mesh, None, rows_arr,
                                                  [], k)
            counts, idxs = self._timed_device_leg(
                run, len(ids), len(slices), cold_rows=cold,
                streaming=False)
        except Exception as e:  # noqa: BLE001 - device trouble ≠ node down
            self._note_device_fallback("topn_topk", e)
            return None
        return [Pair(ids[i], cnt)
                for i, cnt in zip(idxs, counts) if cnt > 0]

    def _topn_host_single_pass(self, index: str, c: Call,
                               slices: list[int],
                               opt: ExecOptions,
                               allow_remote: bool = False,
                               trim: bool = True
                               ) -> Optional[list[Pair]]:
        """The plain sourceless TopN form on a single local node in ONE
        pass over the rank caches, or None for the general path.

        The reference runs two per-slice phases: local tops merged into
        a candidate union, then an exact-count refetch of every
        candidate on every slice (executor.go:273-310). With complete
        per-fragment LRU count caches both phases read the SAME arrays,
        so one walk yields both: the ≥floor prefix feeds a dense
        accumulator (the phase-2 exact sums — per-slice floor applied,
        per reference semantics) and its n-trim marks candidates (the
        phase-1 union). At 1024 slices the two-phase path's second walk
        — per-slice locks, id sorts, membership probes, recounts — was
        the whole superlinear term; this leg is ~linear in slices.

        Safety gates: LRU caches only (RankCache rankings are
        rate-limited-stale and threshold-trimmed; the per-slice path
        reads them with its own staleness rules), caches must not have
        evicted (an evicted row's exact count needs the phase-2
        recount), and pod / remote legs keep the fan-out path. On a
        multi-node cluster the gate is OWNERSHIP, not cluster size:
        when this node holds a replica of every slice, its local rank
        caches cover the whole query (writes fan to every replica
        owner) and the single-pass answer stands.

        ``allow_remote`` lifts the coordinator-only gate for pushdown
        legs (the coordinator explicitly requested node-local
        semantics); ``trim=False`` skips the final top-n trim and
        returns EVERY candidate (the union of per-slice n-trims) with
        its exact sum — the partial-set shape the distributed
        two-phase merge consumes, identical to the candidate set a
        single-node single pass would mark."""
        (frame_name, n, field, row_ids, min_threshold, filters,
         tanimoto) = self._topn_args(c)
        if ((opt.remote and not allow_remote) or row_ids
                or len(c.children) > 0
                or (field and filters) or tanimoto > 0
                or self.pod is not None
                or not self._owns_all_slices(index, slices)):
            return None
        from .storage.cache import LRUCache
        floor = max(min_threshold, 1)
        acc_parts: list[tuple[np.ndarray, np.ndarray, int]] = []
        max_id = 0
        for slice in slices:
            frag = self.holder.fragment(index, frame_name,
                                        VIEW_STANDARD, slice)
            if frag is None:
                continue
            cache = frag.cache
            if (not isinstance(cache, LRUCache)
                    or len(cache) >= cache.max_entries
                    or not frag._cache_complete):
                # Incomplete cache (eviction, or a crash-recovered
                # fragment too big to repair on open): exact counts
                # need the recounting two-phase path.
                return None
            with frag._mu:
                ids, counts = cache.top_arrays()
            if not len(ids):
                continue
            # counts are rank-sorted descending: the ≥floor set is a
            # prefix (same binary-search cut as fragment.top).
            cut = len(counts) - int(np.searchsorted(
                counts[::-1], floor, side="left"))
            if not cut:
                continue
            ids, counts = ids[:cut], counts[:cut]
            acc_parts.append((ids, counts, min(n, cut) if n else cut))
            m = int(ids.max())
            if m > max_id:
                max_id = m
        if not acc_parts:
            return []
        if max_id < (1 << 24):
            # Dense accumulate: per-slice ids are unique, so fancy
            # assignment sums safely slice by slice; candidate marks
            # come from each slice's n-trimmed prefix.
            sums = np.zeros(max_id + 1, dtype=np.int64)
            cand_mark = np.zeros(max_id + 1, dtype=bool)
            for ids, counts, marks in acc_parts:
                idx = ids.astype(np.int64)
                sums[idx] += counts
                cand_mark[idx[:marks]] = True
            cand = np.flatnonzero(cand_mark)
            cand_sums = sums[cand]
        else:
            all_ids = np.concatenate([p[0] for p in acc_parts])
            all_counts = np.concatenate([p[1] for p in acc_parts])
            uids, inv = np.unique(all_ids, return_inverse=True)
            usums = np.bincount(inv,
                                weights=all_counts).astype(np.int64)
            cand = np.unique(np.concatenate(
                [p[0][:p[2]] for p in acc_parts]))
            cand_sums = usums[np.searchsorted(uids, cand)]
        order = np.lexsort((cand, -cand_sums))
        cand, cand_sums = cand[order], cand_sums[order]
        if n and trim:
            cand, cand_sums = cand[:n], cand_sums[:n]
        return [Pair(i, cnt) for i, cnt in zip(cand.tolist(),
                                               cand_sums.tolist())]

    # -- distributed TopN pushdown (ROADMAP item 3) --------------------------

    @staticmethod
    def _topn_to_dict(res) -> dict:
        """Normalize a pushdown leg's result (Pair list off the wire,
        dict from a local/hedge-merged leg, None) to id→count."""
        if res is None:
            return {}
        if isinstance(res, dict):
            return dict(res)
        return {p.id: p.count for p in res}

    @staticmethod
    def _topn_merge_reduce(prev, v):
        """id→count merge across disjoint-slice partials (the
        _top_n_slices reduce shape, reused by hedge sub-legs)."""
        m = prev or {}
        if isinstance(v, dict):
            for k, cnt in v.items():
                m[k] = m.get(k, 0) + cnt
        elif v:
            for p in v:
                m[p.id] = m.get(p.id, 0) + p.count
        return m

    def _topn_exact_partial(self, index: str, c: Call,
                            slices: list[int],
                            opt: ExecOptions) -> list[Pair]:
        """EXACT node-local TopN partials over ``slices``: the
        single-pass rank-cache walk when its safety gates hold, else
        the full local two-phase (candidate gather + ids refetch).
        Untrimmed — every candidate from the per-slice n-trims rides
        back with its exact sum over these slices, so the coordinator
        merge's candidate union equals what a single node spanning
        all slices would mark.

        ``hints`` (an internal arg the coordinator stamps on pushdown
        legs: the candidate ids it already knew when dispatching)
        additionally come back exact-counted in the SAME response — a
        hinted row this node's own trims missed is refetched locally
        here, so a 2-node cluster answers TopN in ONE remote round
        trip instead of two (the round-trip was the measured tax, not
        the compute). A hinted row with no ≥floor count on these
        slices is simply not reported (it contributes zero)."""
        fast = self._topn_host_single_pass(index, c, slices, opt,
                                           allow_remote=True,
                                           trim=False)
        if fast is not None:
            pairs = fast
        else:
            pairs = self._top_n_slices(index, c, slices, opt)
            if pairs:
                other = c.clone()
                other.args.pop("pushdown", None)
                other.args.pop("hints", None)
                other.args["ids"] = sorted({p.id for p in pairs})
                pairs = self._top_n_slices(index, other, slices, opt)
        hints, _ = c.uint_slice_arg("hints")
        if hints:
            have = {p.id for p in pairs}
            missing = sorted(i for i in set(hints) if i not in have)
            if missing:
                other = c.clone()
                other.args.pop("pushdown", None)
                other.args.pop("hints", None)
                other.args["ids"] = missing
                pairs = list(pairs) + self._top_n_slices(
                    index, other, slices, opt)
        return pairs

    def _topn_distributed(self, index: str, c: Call, slices: list[int],
                          opt: ExecOptions,
                          n: int) -> Optional[list[Pair]]:
        """Coordinator side of TopN pushdown, for the plain sourceless
        form on a genuinely distributed index (some slice owned
        elsewhere — locally-covered queries already have the
        single-pass). Each owner runs the single-pass TopN over its
        own slices (``pushdown=true`` legs) and returns untrimmed
        exact (row, count) partials; the coordinator merges per the
        reference two-phase semantics (executor.go:273-310): candidate
        union, then an exact-count refetch ONLY for (node, rows the
        node didn't report) — not all rows on all slices. Failed legs
        re-map onto replicas; hedging composes per leg (the winner's
        partial AND generation tokens count, fault subsystem). Any
        non-lifecycle failure degrades to the fan-out path (None) —
        reads are idempotent, so a partial pushdown attempt is only
        spent work, never a wrong answer."""
        if (not self._topn_pushdown or self.pod is not None
                or opt.partial or self.client is None or not slices
                or len(self.cluster.nodes) < 2
                or not getattr(self.client, "generation_aware", False)
                or self._owns_all_slices(index, slices)):
            return None
        (frame_name, _n, field, row_ids, _thresh, filters,
         tanimoto) = self._topn_args(c)
        if row_ids or c.children or (field and filters) or tanimoto > 0:
            return None
        try:
            with sched_context.span("topn_pushdown",
                                    slices=len(slices)):
                legs = self._topn_pushdown_gather(index, c, slices, opt)
                merged = self._topn_pushdown_merge(index, c, legs, opt)
        except (QueryDeadlineError, QueryCancelledError):
            raise
        except Exception:  # noqa: BLE001 - fan-out path owns failures
            obs_metrics.TOPN_PUSHDOWN.labels("fallback").inc()
            return None
        obs_metrics.TOPN_PUSHDOWN.labels("merged").inc()
        out = pairs_sort([Pair(i, cnt) for i, cnt in merged.items()
                          if cnt > 0])
        # Remember the merged candidate union (top slice of it) as the
        # next query's speculative hints — bounded per entry and per
        # memo so hot frames stay one-round.
        memo = self._topn_hint_memo
        memo[(index, frame_name)] = tuple(p.id for p in out[:1024])
        memo.move_to_end((index, frame_name))
        while len(memo) > 64:
            memo.popitem(last=False)
        if n and n < len(out):
            out = out[:n]
        return out

    def _topn_pushdown_gather(self, index: str, c: Call,
                              slices: list[int],
                              opt: ExecOptions) -> list[tuple]:
        """Dispatch one pushdown leg per owning node; returns
        [(node, group_slices, id→count, hinted_ids)] with
        _map_reduce's failover semantics (a failed leg's slices re-map
        onto surviving replicas through the breaker-ordered
        placement).

        Remote legs dispatch IMMEDIATELY with SPECULATIVE hints — the
        last merged candidate union for this (index, frame), kept in
        a small memo — and the local partial computes concurrently on
        this thread. Hints are only hints: a hinted row the leg
        doesn't hold reads as zero, and a candidate the speculation
        missed is refetched in a second round — so a cold or stale
        memo costs one extra round trip, never a wrong answer. Warm
        (the repeated-query steady state), the whole distributed TopN
        is ONE remote round trip fully overlapped with local work —
        the round-trip is the measured cluster tax, not the
        compute."""
        nodes = list(self.cluster.nodes)
        ctx = opt.ctx
        pool = self._pool("node")
        futures: dict = {}
        legs: list[tuple] = []
        processed = 0
        groups = self._slices_by_node(nodes, index, slices)
        local_groups: list[tuple] = []
        remote_slices: list[int] = []
        for node, group in groups:
            if node.host == self.host:
                local_groups.append((node, group))
            else:
                remote_slices.extend(group)
        if not remote_slices:
            for node, group in local_groups:
                m = self._topn_to_dict(
                    self._topn_exact_partial(index, c, group, opt))
                legs.append((node, group, m, frozenset()))
            return legs
        frame_name = self._topn_args(c)[0]
        hints = sorted(self._topn_hint_memo.get((index, frame_name),
                                                ()))
        c_pd = c.clone()
        c_pd.args["pushdown"] = True
        if hints:
            c_pd.args["hints"] = hints
        hinted = frozenset(hints)

        def submit(nodes, slices):
            for node, group in self._slices_by_node(nodes, index,
                                                    slices):
                fut = pool.submit(self._topn_pushdown_node, node,
                                  index, c, c_pd, group, opt)
                futures[fut] = (node, group)
                if ctx is not None:
                    ctx.add_leg(node.host, len(group))

        try:
            submit(nodes, remote_slices)
            # Local partials overlap the in-flight remote legs.
            for node, group in local_groups:
                m = self._topn_to_dict(
                    self._topn_exact_partial(index, c, group, opt))
                legs.append((node, group, m, frozenset()))
            while processed < len(remote_slices):
                if ctx is None:
                    done, _ = wait(list(futures),
                                   return_when=FIRST_COMPLETED)
                else:
                    ctx.check()
                    done, _ = wait(list(futures),
                                   timeout=self._CTX_POLL_S,
                                   return_when=FIRST_COMPLETED)
                for fut in done:
                    node, group = futures.pop(fut)
                    try:
                        r = fut.result()
                    except (QueryDeadlineError, QueryCancelledError):
                        raise
                    except Exception as e:  # noqa: BLE001 - re-map
                        nodes = [x for x in nodes if x is not node]
                        obs_metrics.FAILOVER_SLICES.labels(
                            node.host or "local").inc(len(group))
                        if ctx is not None:
                            # Tail sampling: a failover leg is keep-
                            # worthy evidence (obs.sampler "breaker").
                            ctx.note_flag("failover")
                        with sched_context.span(
                                "failover", peer=node.host,
                                slices=len(group),
                                error=type(e).__name__):
                            pass
                        try:
                            submit(nodes, group)
                        except SliceUnavailableError:
                            raise e
                        continue
                    legs.append((node, group, r, hinted))
                    processed += len(group)
        finally:
            pending = [f for f in futures if not f.cancel()]
            if pending:
                if ctx is not None and (ctx.cancelled()
                                        or ctx.expired()):
                    wait(pending, timeout=self._DEAD_DRAIN_S)
                else:
                    wait(pending)
        return legs

    def _topn_pushdown_node(self, node: Node, index: str, c: Call,
                            c_pd: Call, group: list[int],
                            opt: ExecOptions) -> dict:
        """One node's exact partial. Remote legs forward ``c_pd``
        (the call with the ``pushdown`` marker + the coordinator's
        candidate hints), which makes the peer run the whole TopN
        algorithm over its own slices and answer exact untrimmed
        partials INCLUDING the hinted rows (the leg contract the
        merge relies on). A leg re-mapped onto the local replica runs
        the same c_pd semantics in-process, so the hinted-coverage
        bookkeeping stays uniform. Hedging composes: the hedge race
        duplicates the pushdown leg at surviving replicas, first
        response wins, and only the winner's generation tokens reach
        the map."""
        with sched_context.use(opt.ctx):
            if opt.ctx is not None:
                opt.ctx.check()
            if node.host == self.host:
                with sched_context.stage("leg",
                                         host=node.host or "local",
                                         slices=len(group)):
                    return self._topn_to_dict(
                        self._topn_exact_partial(index, c_pd, group,
                                                 opt))
            hedge_s = (self.fault.hedge_delay_s(node.host)
                       if self.fault is not None else None)
            if hedge_s:
                res = self._exec_remote_hedged(
                    node, index, c_pd, group, opt, None,
                    self._topn_merge_reduce, hedge_s,
                    local_fn=lambda sl: self._topn_exact_partial(
                        index, c_pd, sl, opt))
            else:
                rs = self._exec_remote(node, index, Query([c_pd]),
                                       group, opt)
                res = rs[0] if rs else None
            return self._topn_to_dict(res)

    def _topn_pushdown_merge(self, index: str, c: Call,
                             legs: list[tuple],
                             opt: ExecOptions) -> dict:
        """Two-phase merge of per-node partials: sum what every node
        reported, then refetch exact counts ONLY for (node, rows in
        the union that node didn't report AND wasn't hinted about) — a
        row trimmed out (or absent) on one node still collects its
        counts there before the global trim. Hinted rows are already
        covered by the leg's own response (zero if unreported), so on
        a 2-node cluster the refetch set is empty by construction —
        except the coordinator's own leg, whose refetch is in-process
        and pays no round trip."""
        union: set = set()
        for _node, _group, m, _hinted in legs:
            union.update(m)
        total: dict = {}
        for _node, _group, m, _hinted in legs:
            for k, cnt in m.items():
                total[k] = total.get(k, 0) + cnt
        jobs = []
        for node, group, m, hinted in legs:
            missing = sorted(i for i in union
                             if i not in m and i not in hinted)
            if missing:
                jobs.append((node, group, missing))
        if not jobs:
            return total
        pool = self._pool("node")
        futs = [pool.submit(self._topn_refetch_leg, node, index, c,
                            group, missing, opt)
                for node, group, missing in jobs]
        try:
            for fut in futs:
                m = fut.result()
                for k, cnt in m.items():
                    total[k] = total.get(k, 0) + cnt
        finally:
            pending = [f for f in futs if not f.cancel()]
            if pending:
                wait(pending)
        return total

    def _topn_refetch_leg(self, node: Node, index: str, c: Call,
                          group: list[int], ids: list[int],
                          opt: ExecOptions) -> dict:
        """Exact counts for ``ids`` over one node's slices (the
        reference phase-2 shape, restricted to the rows that node is
        missing)."""
        with sched_context.use(opt.ctx):
            if opt.ctx is not None:
                opt.ctx.check()
            other = c.clone()
            other.args.pop("pushdown", None)
            other.args["ids"] = [int(i) for i in ids]
            if node.host == self.host:
                return self._topn_to_dict(
                    self._top_n_slices(index, other, group, opt))
            rs = self._exec_remote(node, index, Query([other]), group,
                                   opt)
            return self._topn_to_dict(rs[0] if rs else None)

    def _top_n_slices(self, index: str, c: Call, slices: list[int],
                      opt: ExecOptions) -> list[Pair]:
        def map_fn(slice):
            return self._top_n_slice(index, c, slice)

        def reduce_fn(prev, v):
            # Accumulate id→count in a plain dict across the whole
            # reduce chain and materialize Pairs ONCE at the end —
            # pairs_add's rebuild-a-Pair-list-per-merge costs O(total)
            # per step, which at 256 slices × ~200 candidates was a
            # third of the query (cache.go:343-361 semantics kept).
            # prev is always None or a prior return of this function;
            # v is a dict (pre-reduced group) or a leg's Pair list.
            m = prev or {}
            if isinstance(v, dict):
                for k, cnt in v.items():
                    m[k] = m.get(k, 0) + cnt
            elif v:
                for p in v:
                    m[p.id] = m.get(p.id, 0) + p.count
            return m

        device_fn = self._topn_local_device_fn(index, c, opt)
        host_fn = self._topn_local_host_fn(index, c)

        def local_fn(batch: list[int]):
            if device_fn is not None:
                out = device_fn(batch)
                if out is not NotImplemented:
                    return out
            if host_fn is not None:
                return host_fn(batch)
            return NotImplemented

        merged = self._map_reduce(index, slices, c, opt, map_fn,
                                  reduce_fn, local_fn=local_fn)
        if isinstance(merged, dict):
            merged = [Pair(i, cnt) for i, cnt in merged.items()]
        return pairs_sort(merged or [])

    def _topn_local_device_fn(self, index: str, c: Call, opt: ExecOptions):
        """Batched local-leg TopN exact-count phase: ALL candidate rows ×
        ALL slices in one psum-reduced mesh program.

        Eligible for the with-source exact-count forms — explicit
        candidate ids plus a device-compilable source bitmap. The plain
        form is a mesh reduction (parallel.mesh.topn_exact); threshold>1
        and Tanimoto run the per-slice pruning on device
        (mesh.topn_filtered_sharded, fragment.go:560-614 semantics);
        attribute filters drop candidates host-side first (row attrs
        are frame-global, so pre-filtering ids is exactly the per-slice
        filter). The ids-without-source form stays host-side on
        purpose: there the per-slice path answers from RankCache
        counts, and the device's fresh popcounts could disagree with a
        stale cache entry. Everything else keeps the per-slice path,
        which owns the full semantics.
        """
        if not self.use_mesh:
            return None
        if self.pod is None and self._mesh_backoff_active():
            return None
        row_ids, _ = c.uint_slice_arg("ids")
        if not row_ids:
            return None  # candidate-selection phase reads rank caches
        min_threshold, _ = c.uint_arg("threshold")
        tanimoto, _ = c.uint_arg("tanimotoThreshold")
        if tanimoto > 100:
            return None  # host path owns the error semantics
        field = c.args.get("field")
        filters = c.args.get("filters")
        if len(c.children) != 1:
            return None
        frame_name = c.args.get("frame") or DEFAULT_FRAME
        leaves: list[tuple] = []
        expr = self._compile_device_expr(index, c.children[0], leaves)
        if expr is None:
            return None
        threshold = max(min_threshold, MIN_THRESHOLD)
        if self.pod is not None:
            if not self.pod.is_coordinator or opt.pod_local:
                return None  # plain local path on pod-internal legs

            def pod_fn(slices: list[int]):
                ids = self._attr_filtered_ids(index, frame_name, row_ids,
                                              field, filters)
                if ids is None:
                    return NotImplemented
                if not ids:
                    return []
                from .ops.packed import WORDS_PER_SLICE
                # Same host-allocation guard as the single-process path,
                # per pod process (every process densifies its shard).
                if (len(slices) < self.mesh_min_slices
                        or self.pod.max_shard_slices(slices) * len(ids)
                        * WORDS_PER_SLICE * 4 > self._TOPN_HOST_BLOCK_BYTES):
                    return NotImplemented
                try:
                    counts = self.pod.topn_exact(
                        index, frame_name, expr, leaves, ids, slices,
                        threshold=threshold, tanimoto=tanimoto)
                except Exception as e:  # noqa: BLE001 - pod host fan-out
                    self._note_device_fallback("pod.topn_exact", e)
                    return NotImplemented  # correct via _pod_host_mapper
                return [Pair(rid, cnt)
                        for rid, cnt in zip(ids, counts) if cnt > 0]
            return pod_fn

        def local_fn(slices: list[int]):
            if len(slices) < self.mesh_min_slices:
                return NotImplemented
            ids = self._attr_filtered_ids(index, frame_name, row_ids,
                                          field, filters)
            if ids is None:
                return NotImplemented
            if not ids:
                return []
            from .ops.packed import WORDS_PER_SLICE
            # Host-allocation guard: huge candidate sets stay on the
            # per-slice path, which never materializes a dense block.
            block_bytes = len(slices) * len(ids) * WORDS_PER_SLICE * 4
            if block_bytes > self._TOPN_HOST_BLOCK_BYTES:
                return NotImplemented
            mesh = self._mesh_or_none()
            if mesh is None:
                return NotImplemented
            from .parallel import mesh as mesh_mod
            from .parallel.residency import device_cache
            resident_ok = (len(slices) <= mesh_mod.slice_chunk_bound(
                mesh.shape[mesh_mod.AXIS_SLICES])
                and block_bytes <= self._topn_resident_bytes())
            # Cold estimate: the candidate block (the dominant upload)
            # counts as cold unless it is already resident; the
            # streaming form re-packs it every query, so it is always
            # cold there. Leaf slabs add their own cold rows.
            rows_key = self._topn_rows_key(mesh, index, frame_name,
                                           tuple(ids), slices)
            keys, found, cold = self._leaf_lookup(mesh, index, leaves,
                                                  slices)
            if not (resident_ok and device_cache().contains(rows_key)):
                cold += len(ids)
            if not self._device_pays(mesh, len(ids) + len(leaves),
                                     len(slices), cold_rows=cold,
                                     streaming=not resident_ok):
                return NotImplemented  # calibrated: host clearly faster
            try:
                def run():
                    if resident_ok:
                        return self._topn_exact_resident(
                            mesh, index, frame_name, expr, leaves,
                            tuple(ids), slices, threshold,
                            tanimoto, rows_key, (keys, found))
                    return mesh_mod.topn_exact(
                        mesh, expr,
                        self._pack_candidate_rows(index, frame_name,
                                                  ids, slices),
                        self._pack_leaf_block(index, leaves, slices),
                        threshold=threshold, tanimoto=tanimoto)
                # Same drift feedback the Count device leg gets — the
                # TopN exact phase is the other big routed surface.
                # The streaming form records under its own leg: the
                # prediction now prices the per-query host-side block
                # packing (Calibration.pack_bps), so its samples feed
                # correction instead of being excluded.
                counts = self._timed_device_leg(
                    run, len(ids) + len(leaves), len(slices),
                    cold_rows=cold, streaming=not resident_ok)
            except Exception as e:  # noqa: BLE001 - device trouble ≠ node down
                self._note_device_fallback("topn_exact", e)
                return NotImplemented
            return [Pair(rid, cnt)
                    for rid, cnt in zip(ids, counts) if cnt > 0]

        return local_fn

    def _attr_filtered_ids(self, index: str, frame_name: str,
                           row_ids, field, filters) -> Optional[list[int]]:
        """Candidate ids surviving the attribute filter. Row attrs are
        frame-global, so pre-filtering equals the per-slice filter
        (fragment.top). None = no attr store (caller falls back)."""
        if not (field and filters):
            return list(row_ids)
        frame = self.holder.frame(index, frame_name)
        store = frame.row_attr_store if frame else None
        if store is None:
            return None
        fset = set(filters)
        return [rid for rid in row_ids
                if (val := (store.attrs(rid) or {}).get(field))
                is not None and val in fset]

    def _pack_candidate_rows(self, index: str, frame_name: str,
                             row_ids: list[int],
                             slices: list[int]) -> np.ndarray:
        """[n_slices, n_rows, words] dense candidate block, host-side."""
        from .ops.packed import WORDS_PER_SLICE
        rows = np.zeros((len(slices), len(row_ids), WORDS_PER_SLICE),
                        dtype=np.uint32)
        for si, slice in enumerate(slices):
            frag = self.holder.fragment(index, frame_name,
                                        VIEW_STANDARD, slice)
            if frag is None:
                continue
            # Bypass the packed-row LRU when this candidate set
            # exceeds the fragment's own budget (0% hit rate, pure
            # churn against the hot leaf rows).
            cached = len(row_ids) <= frag.device.max_rows
            for ri, rid in enumerate(row_ids):
                frag.pack_row(rid, out=rows[si, ri], cached=cached)
        return rows

    def _topn_rows_key(self, mesh, index: str, frame_name: str,
                       row_ids: tuple[int, ...], slices) -> tuple:
        """Residency key of a TopN candidate block: the leaf key's
        shape over the frame's standard view, rows in place of a row."""
        from .parallel import mesh as mesh_mod
        uid, gen = self._view_token(index, frame_name, VIEW_STANDARD,
                                    slices)
        return ("topnrows", id(self.holder), index, frame_name, row_ids,
                self._slices_key(slices), uid, gen,
                mesh.shape[mesh_mod.AXIS_SLICES])

    def _topn_exact_resident(self, mesh, index: str, frame_name: str,
                             expr, leaves: list[tuple],
                             row_ids: tuple[int, ...], slices,
                             threshold: int, tanimoto: int,
                             rows_key: tuple, looked) -> list[int]:
        """TopN exact counts with the candidate block and leaf slabs
        device-resident (budgeted HBM cache) — repeat TopN queries skip
        the per-query pack + upload entirely. threshold>1 / tanimoto
        engage the per-slice pruning program (mesh.topn_filtered_sharded)."""
        from .parallel import mesh as mesh_mod
        from .parallel import residency
        rows_arr = residency.candidate_block(
            mesh, rows_key,
            self._key_frags(index, frame_name, VIEW_STANDARD, slices,
                            rows_key), row_ids)
        leaf_arrays = self._leaf_device_arrays(mesh, index, leaves,
                                               slices, looked)
        if threshold > 1 or tanimoto > 0:
            return mesh_mod.topn_filtered_sharded(
                mesh, expr, rows_arr, leaf_arrays,
                threshold=threshold, tanimoto=tanimoto)
        return mesh_mod.topn_exact_sharded(mesh, expr, rows_arr,
                                           leaf_arrays)

    def _topn_local_host_fn(self, index: str, c: Call):
        """Vectorized host leg for the sourceless TopN forms: one
        rank-array pass per fragment, merged as a single id→count dict
        (the reduce_fn's pre-reduced-group shape). The per-slice
        map_fn path builds a Pair per (slice, candidate) — ~4 M Python
        objects at 1024 slices × 1000 candidates, measured ~2.4 s p50;
        this leg replays the same per-slice semantics (floor, then
        per-slice n-trim for the plain form) in numpy, ~50 ms.
        Returns None when the form needs the general path (source
        bitmap, attribute filters, Tanimoto)."""
        (frame_name, n, field, row_ids, min_threshold, filters,
         tanimoto) = self._topn_args(c)
        if (len(c.children) > 0 or (field and filters) or tanimoto > 0):
            return None
        if self.pod is not None:
            # Pod processes shard fragments pod-internally: a batch here
            # includes slices whose data lives on OTHER processes, which
            # this leg would silently count as empty — the podLocal
            # host mapper owns that fan-out.
            return None

        def host_fn(batch: list[int]):
            import numpy as np

            from .storage.cache import LRUCache
            floor = max(min_threshold, 1)
            merged_ids: list[np.ndarray] = []
            merged_counts: list[np.ndarray] = []
            row_arr = (np.asarray(sorted(row_ids), dtype=np.uint64)
                       if row_ids else None)
            for slice in batch:
                frag = self.holder.fragment(index, frame_name,
                                            VIEW_STANDARD, slice)
                if frag is None or not hasattr(frag.cache,
                                               "top_arrays"):
                    continue
                if row_arr is not None and not isinstance(frag.cache,
                                                          LRUCache):
                    # RankCache rankings are rate-limited (stale up to
                    # 10 s) and threshold-trimmed; the per-slice path's
                    # cache.get() reads fresh entries — only the LRU
                    # cache's arrays are equivalent to get() (review
                    # finding: ranked frames returned stale counts).
                    return NotImplemented
                if getattr(frag, "tier_state", "hot") != "hot":
                    # TopN ranks through the count cache, which
                    # demotion drops — a cold/blob fragment must fully
                    # promote (rebuilding the rank cache) before its
                    # arrays mean anything, same contract as the
                    # per-slice fragment.top gate.
                    frag.promote(trigger="read")
                # Same lock the per-slice fragment.top path holds:
                # cache recalculation and the positions walk race
                # concurrent writers otherwise.
                with frag._mu:
                    frag.cache.invalidate()
                    ids, counts = frag.cache.top_arrays()
                    if row_arr is None:
                        # plain form: the ≥-floor prefix, then the
                        # per-slice n trim (fragment.top's array path).
                        cut = len(counts) - int(np.searchsorted(
                            counts[::-1], floor, side="left"))
                        ids, counts = ids[:cut], counts[:cut]
                        if n:
                            ids, counts = ids[:n], counts[:n]
                    elif len(ids) == 0:
                        # empty cache (e.g. lost sidecar): every
                        # candidate goes through the recount fallback.
                        ids, counts = self._topn_recount(
                            frag, row_arr,
                            np.zeros(len(row_arr), np.int64),
                            np.arange(len(row_arr)), floor)
                    else:
                        # ids form (the exact-count refetch):
                        # per-slice counts per candidate; cache misses
                        # with bits recount via row_count
                        # (fragment._top_pairs semantics) + the
                        # per-slice floor.
                        order = np.argsort(ids)
                        sids, scounts = ids[order], counts[order]
                        pos = np.minimum(
                            np.searchsorted(sids, row_arr),
                            len(sids) - 1)
                        hit = sids[pos] == row_arr
                        got = np.where(hit, scounts[pos],
                                       0).astype(np.int64)
                        missing = np.flatnonzero(~hit | (got <= 0))
                        ids, counts = self._topn_recount(
                            frag, row_arr, got, missing, floor)
                if len(ids):
                    merged_ids.append(ids.astype(np.uint64))
                    merged_counts.append(counts.astype(np.int64))
            if not merged_ids:
                return {}
            all_ids = np.concatenate(merged_ids)
            all_counts = np.concatenate(merged_counts)
            uids, inv = np.unique(all_ids, return_inverse=True)
            sums = np.bincount(inv, weights=all_counts).astype(np.int64)
            return dict(zip(uids.tolist(), sums.tolist()))

        return host_fn

    @staticmethod
    def _topn_recount(frag, row_arr, got, missing, floor):
        """Recount the ``missing`` candidate positions of ``got`` via
        row_count — but only for rows that actually have bits here
        (fragment.present_rows; the blind per-id recount was ~900 K
        walks at 1024 slices). Returns the ≥-floor (ids, counts).
        Caller holds frag._mu."""
        if len(missing):
            present = frag.present_rows()
            if present is not None:
                have = np.isin(row_arr[missing], present)
                missing = missing[have]
            if len(missing):
                got = got.copy()
                for mi in missing.tolist():
                    got[mi] = frag.row_count(int(row_arr[mi]))
        keep = got >= floor
        return row_arr[keep], got[keep]

    def _top_n_slice(self, index: str, c: Call, slice: int) -> list[Pair]:
        # executor.go:325-396. Args parse once per call object, not per
        # slice — a 256-slice fan-out re-converting a 1000-entry ids
        # list per slice per phase was measurable.
        parsed = self._topn_args(c)
        (frame_name, n, field, row_ids, min_threshold, filters,
         tanimoto) = parsed

        src = None
        if len(c.children) == 1:
            src = self._bitmap_call_slice(index, c.children[0], slice)
        elif len(c.children) > 1:
            raise PilosaError("TopN() can only have one input bitmap")

        frag = self.holder.fragment(index, frame_name, VIEW_STANDARD, slice)
        if frag is None:
            return []
        # Validation ordering matches the reference: tanimoto bounds
        # are checked by Fragment.Top AFTER the nil-fragment return
        # (fragment.go:490-625) — a bad threshold against a missing
        # fragment is an empty result, not an error.
        if tanimoto > 100:
            raise PilosaError("Tanimoto Threshold is from 1 to 100 only")
        return frag.top(TopOptions(
            n=n, src=src, row_ids=row_ids, filter_field=field,
            filter_values=filters, min_threshold=min_threshold,
            tanimoto_threshold=tanimoto))

    def _topn_args(self, c: Call):
        """Parsed TopN arguments, memoized on the Call object (one
        query evaluates the same immutable call across many slices and
        two phases). Pure parsing only — value validation stays in
        _top_n_slice to preserve the reference's error ordering."""
        parsed = getattr(c, "_topn_parsed", None)
        if parsed is not None:
            return parsed
        frame_name = c.args.get("frame") or DEFAULT_FRAME
        n, _ = c.uint_arg("n")
        field = c.args.get("field", "")
        row_ids, _ = c.uint_slice_arg("ids")
        min_threshold, _ = c.uint_arg("threshold")
        filters = c.args.get("filters") or []
        tanimoto, _ = c.uint_arg("tanimotoThreshold")
        if min_threshold <= 0:
            min_threshold = MIN_THRESHOLD
        parsed = (frame_name, n, field, row_ids, min_threshold, filters,
                  tanimoto)
        c._topn_parsed = parsed
        return parsed

    # -- writes (executor.go:600-797) ----------------------------------------

    # Minimum consecutive same-kind mutation calls before the batched
    # write path engages (below this the per-op path's fixed cost wins).
    _BATCH_MIN_MUTATES = 8

    def _mutate_batch_run(self, index: str, calls: list[Call], start: int,
                          opt: ExecOptions):
        """(results, n_calls) for a maximal run of consecutive
        timestamp-free SetBit (or ClearBit) calls, applied through the
        fragments' native batch engine — ONE native crossing + ONE WAL
        group-commit per touched fragment. Only fully-LOCAL runs batch:
        if any leg would forward to a remote node or another pod
        process, the run falls back to the per-op path, whose
        apply-prefix-then-raise semantics on a mid-stream forwarding
        failure are the reference's (executor.go:664-691,768-797) —
        a batch that had already applied local mutations for later
        calls would otherwise break execute_partial's prefix contract
        (review r5). Also falls back (None) on anything unusual —
        wrong view, timestamps, missing args — so error semantics stay
        exactly per-op."""
        name = calls[start].name
        if name not in ("SetBit", "ClearBit"):
            return None
        n = len(calls)
        j = start
        while (j < n and calls[j].name == name
               and "timestamp" not in calls[j].args
               and calls[j].args.get("view", "") in
               ("", VIEW_STANDARD, VIEW_INVERSE)):
            j += 1
        count = j - start
        if count < self._BATCH_MIN_MUTATES:
            return None
        run = calls[start:j]
        set_ = name == "SetBit"
        idx_obj = self.holder.index(index)
        if idx_obj is None:
            raise IndexNotFoundError(index)

        # Parse phase — nothing is applied until every call parses, so
        # a fallback to the per-op path never double-applies.
        frames: dict[str, object] = {}
        ops: list[tuple] = []  # (k, frame_name, row, col, view)
        for k, c in enumerate(run):
            fname = c.args.get("frame")
            if not fname:
                return None
            frame = frames.get(fname)
            if frame is None:
                frame = idx_obj.frame(fname)
                if frame is None:
                    return None
                frames[fname] = frame
            try:
                row_id, ok = c.uint_arg(frame.row_label)
                col_id, ok2 = c.uint_arg(idx_obj.column_label)
            except (PilosaError, ValueError, TypeError):
                # Non-integer id value: fall back so the per-op path
                # applies the prefix then raises, exactly like the
                # reference's sequential loop.
                return None
            if not (ok and ok2):
                return None
            ops.append((k, fname, row_id, col_id,
                        c.args.get("view", "")))

        results = [False] * count
        # view-ops: (call_k, frame_name, view, axis_row, axis_col) where
        # axis_col routes the slice (for inverse views that is the
        # original row id — executor.go:744-745).
        vops: list[tuple] = []
        for k, fname, row_id, col_id, view in ops:
            frame = frames[fname]
            if view in ("", VIEW_STANDARD):
                vops.append((k, fname, VIEW_STANDARD, row_id, col_id))
            if (view == VIEW_INVERSE
                    or (view == "" and frame.inverse_enabled)):
                vops.append((k, fname, VIEW_INVERSE, col_id, row_id))

        local_groups: dict[tuple, list] = {}   # (frame, view) -> [vop]
        for vop in vops:
            k, fname, view, axis_row, axis_col = vop
            slice = axis_col // SLICE_WIDTH
            for node in self.cluster.fragment_nodes(index, slice):
                if node.host == self.host:
                    if (self.pod is not None and not opt.pod_local
                            and self.pod.owner_pid(slice)
                            != self.pod.pid):
                        return None  # pod-forwarded leg: per-op path
                    local_groups.setdefault((fname, view),
                                            []).append(vop)
                    continue
                if not opt.remote:
                    return None  # remote replica leg: per-op path

        for (fname, view), group in local_groups.items():
            rows = np.fromiter((g[3] for g in group), np.uint64,
                               len(group))
            cols = np.fromiter((g[4] for g in group), np.uint64,
                               len(group))
            changed = frames[fname].mutate_bits(view, rows, cols, set_)
            for g, ch in zip(group, changed.tolist()):
                if ch:
                    results[g[0]] = True

        return results, count

    def _point_mutate_fast(self, index: str, m, opt: ExecOptions
                           ) -> Optional[list]:
        """The string lane's warm half: a ``_POINT_MUTATE_RE`` match
        plus a hot ``_wfast_frag`` entry go straight to the fragment
        mutate. Returns None on any miss — cold cache, closed
        fragment, non-default labels (ent[2]), or a cluster that is
        no longer this single node — and the generic path (which owns
        errors and cache population) re-runs the op from the string."""
        col_id = int(m.group(4))
        ent = self._wfast_frag.get(
            (index, m.group(2), col_id // SLICE_WIDTH))
        if ent is None or not ent[2] or not ent[1]._open:
            return None
        nodes = self.cluster.nodes
        if (len(nodes) != 1 or nodes[0].host != self.host
                or self.cluster.resize is not None):
            # An in-flight resize (1→2 grow) means even a single-node
            # cluster's writes must fan to the union — generic path.
            return None
        if opt.ctx is not None:
            opt.ctx.check()
        frag = ent[1]
        if m.group(1) == "SetBit":
            return [frag.set_bit(int(m.group(3)), col_id)]
        return [frag.clear_bit(int(m.group(3)), col_id)]

    def _execute_set_bit(self, index: str, c: Call, opt: ExecOptions
                         ) -> bool:
        return self._execute_mutate_bit(index, c, opt, set=True)

    def _execute_clear_bit(self, index: str, c: Call, opt: ExecOptions
                           ) -> bool:
        return self._execute_mutate_bit(index, c, opt, set=False)

    def _execute_mutate_bit(self, index: str, c: Call, opt: ExecOptions,
                            set: bool) -> bool:
        # Per-op write fast lane: the production single-op shape
        # (standard view, no timestamp, single-node non-pod cluster,
        # this node the sole owner) resolves (index, frame, slice) ->
        # Fragment through a small cache instead of re-walking
        # placement hashing + frame -> view -> fragment locks per op —
        # the walk cost more than the mutate itself (ISSUE 8). Any
        # unusual shape falls through to the generic path below, which
        # also owns every error message.
        args = c.args
        if ("timestamp" not in args and not args.get("view")
                and self.pod is None
                and self.cluster.resize is None):
            nodes = self.cluster.nodes
            if len(nodes) == 1 and nodes[0].host == self.host:
                idx = self.holder.index(index)
                fname = args.get("frame")
                frame = (idx.frame(fname)
                         if idx is not None and fname else None)
                if frame is not None and not frame.inverse_enabled:
                    row_id = args.get(frame.row_label)
                    col_id = args.get(idx.column_label)
                    if (type(row_id) is int and type(col_id) is int
                            and row_id >= 0 and col_id >= 0):
                        fkey = (index, fname, col_id // SLICE_WIDTH)
                        ent = self._wfast_frag.get(fkey)
                        if (ent is None or ent[0] is not frame
                                or not ent[1]._open):
                            v = frame.create_view_if_not_exists(
                                VIEW_STANDARD)
                            # Third slot: the string lane's one-read
                            # precondition — default labels, so the
                            # regex's literal rowID/columnID keys are
                            # the frame's actual labels (inverse off
                            # is already a condition of being here,
                            # and label/inverse options are fixed at
                            # frame creation).
                            ent = (frame,
                                   v.create_fragment_if_not_exists(
                                       fkey[2]),
                                   frame.row_label == "rowID"
                                   and idx.column_label == "columnID")
                            if len(self._wfast_frag) >= 4096:
                                # Bound the cache without per-op LRU
                                # bookkeeping on the hot read: drop it
                                # wholesale (rebuilds in a few ops) so
                                # entries for deleted frames can't pin
                                # closed fragments forever.
                                self._wfast_frag.clear()
                            self._wfast_frag[fkey] = ent
                        frag = ent[1]
                        return (frag.set_bit(row_id, col_id) if set
                                else frag.clear_bit(row_id, col_id))
        name = "SetBit" if set else "ClearBit"
        view = c.args.get("view", "")
        frame_name = c.args.get("frame")
        if not frame_name:
            raise PilosaError(f"{name}() frame required")
        idx = self.holder.index(index)
        if idx is None:
            raise IndexNotFoundError(index)
        frame = idx.frame(frame_name)
        if frame is None:
            raise FrameNotFoundError(frame_name)

        row_id, ok = c.uint_arg(frame.row_label)
        if not ok:
            raise PilosaError(
                f"{name}() row field '{frame.row_label}' required")
        col_id, ok = c.uint_arg(idx.column_label)
        if not ok:
            raise PilosaError(
                f"{name}() column field '{idx.column_label}' required")
        timestamp = _parse_timestamp(c) if set else None

        if view == VIEW_STANDARD:
            return self._mutate_bit_view(index, c, frame, view, col_id,
                                         row_id, timestamp, opt, set)
        if view == VIEW_INVERSE:
            return self._mutate_bit_view(index, c, frame, view, row_id,
                                         col_id, timestamp, opt, set)
        if view == "":
            ret = self._mutate_bit_view(index, c, frame, VIEW_STANDARD,
                                        col_id, row_id, timestamp, opt, set)
            if frame.inverse_enabled:
                if self._mutate_bit_view(index, c, frame, VIEW_INVERSE,
                                         row_id, col_id, timestamp, opt,
                                         set):
                    ret = True
            return ret
        raise PilosaError(f"invalid view: {view}")

    def _mutate_bit_view(self, index: str, c: Call, frame, view: str,
                         col_id: int, row_id: int,
                         timestamp: Optional[dt.datetime], opt: ExecOptions,
                         set: bool) -> bool:
        # Route to every replica owner of the slice (executor.go:664-691,
        # 768-797). In the view axis convention, col_id is the id that
        # chooses the slice (for inverse views that is the original row id).
        from . import SLICE_WIDTH
        slice = col_id // SLICE_WIDTH
        ret = False
        for node in self.cluster.fragment_nodes(index, slice):
            if node.host == self.host:
                if (self.pod is not None and not opt.pod_local
                        and self.pod.owner_pid(slice) != self.pod.pid):
                    # This pod owns the slice, but a different pod
                    # process holds it — forward the single-view call
                    # as a podLocal leg (parallel.pod placement).
                    if self._pod_write_remote(index, c, view, slice):
                        ret = True
                    continue
                op = frame.set_bit if set else frame.clear_bit
                if op(view, row_id, col_id, timestamp):
                    ret = True
                continue
            if opt.remote:
                continue
            res = self._exec_remote(node, index, Query([c]), None, opt)
            if res and res[0]:
                ret = True
        return ret

    def _pod_write_remote(self, index: str, c: Call, view: str,
                          slice: int) -> bool:
        """Forward one view's bit mutation to the owning pod process and
        remember the slice exists (the coordinator computes query slice
        lists from its own max-slice knowledge)."""
        pid = self.pod.owner_pid(slice)
        other = c.clone()
        other.args["view"] = view  # pin: owner differs per view axis
        if self.client is None:
            raise SliceUnavailableError(
                f"no client to reach pod process {pid}")
        res = self.client.execute_query(
            Node(self.pod.peers[pid]), index, str(Query([other])), None,
            remote=True, pod_local=True)
        idx = self.holder.index(index)
        if idx is not None:
            if view == VIEW_INVERSE:
                idx.set_remote_max_inverse_slice(slice)
            else:
                idx.set_remote_max_slice(slice)
        return bool(res and res[0])

    def _pod_forward_attrs(self, index: str, calls: list[Call],
                           opt: ExecOptions) -> None:
        """Attribute writes replicate to every pod process (workers read
        their own attr stores for TopN filters), even on cluster-remote
        legs — only podLocal legs stop the fan-out."""
        if (self.pod is None or opt.pod_local
                or not self.pod.is_coordinator or self.client is None):
            return
        q = str(Query(list(calls)))
        for pid in range(1, self.pod.n_procs):
            self.client.execute_query(Node(self.pod.peers[pid]), index,
                                      q, None, remote=True, pod_local=True)

    # -- attributes (executor.go:800-988) ------------------------------------

    def _row_attrs_of(self, index: str, c: Call) -> tuple[str, object,
                                                          int, dict]:
        """Resolve a SetRowAttrs call → (frame_name, frame, row_id,
        attrs-minus-reserved-keys)."""
        frame_name = c.args.get("frame")
        if not frame_name:
            raise PilosaError("SetRowAttrs() frame required")
        frame = self.holder.frame(index, frame_name)
        if frame is None:
            raise FrameNotFoundError(frame_name)
        row_id, ok = c.uint_arg(frame.row_label)
        if not ok:
            raise PilosaError(
                f"SetRowAttrs() row field '{frame.row_label}' required")
        attrs = dict(c.args)
        attrs.pop("frame", None)
        attrs.pop(frame.row_label, None)
        return frame_name, frame, row_id, attrs

    def _execute_set_row_attrs(self, index: str, c: Call,
                               opt: ExecOptions) -> None:
        _, frame, row_id, attrs = self._row_attrs_of(index, c)
        frame.row_attr_store.set_attrs(row_id, attrs)
        self._pod_forward_attrs(index, [c], opt)
        self._broadcast_call(index, [c], opt)

    def _execute_bulk_set_row_attrs(self, index: str, calls: list[Call],
                                    opt: ExecOptions) -> list:
        # executor.go:857-941: group attrs by frame/row, bulk insert.
        by_frame: dict[str, dict[int, dict]] = {}
        for c in calls:
            frame_name, _, row_id, attrs = self._row_attrs_of(index, c)
            by_frame.setdefault(frame_name, {}).setdefault(
                row_id, {}).update(attrs)
        for frame_name, rows in by_frame.items():
            self.holder.frame(index, frame_name).row_attr_store \
                .set_bulk_attrs(rows)
        self._pod_forward_attrs(index, calls, opt)
        self._broadcast_call(index, calls, opt)
        return [None] * len(calls)

    def _execute_set_column_attrs(self, index: str, c: Call,
                                  opt: ExecOptions) -> None:
        idx = self.holder.index(index)
        if idx is None:
            raise IndexNotFoundError(index)
        id, ok = c.uint_arg("id")
        col_name = "id"
        if not ok:
            id, ok = c.uint_arg(idx.column_label)
            if not ok:
                raise PilosaError("SetColumnAttrs() id required")
            col_name = idx.column_label
        attrs = dict(c.args)
        attrs.pop(col_name, None)
        idx.column_attr_store.set_attrs(id, attrs)
        self._pod_forward_attrs(index, [c], opt)
        self._broadcast_call(index, [c], opt)

    def _broadcast_call(self, index: str, calls: list[Call],
                        opt: ExecOptions) -> None:
        """Forward attribute writes to every other node in parallel
        (executor.go:836-854)."""
        if opt.remote:
            return
        others = [n for n in self.cluster.nodes if n.host != self.host]
        if not others:
            return
        errs = []
        threads = []
        q = Query(list(calls))

        def run(node):
            try:
                self._exec_remote(node, index, q, None, opt)
            except Exception as e:  # noqa: BLE001 - collected and re-raised
                errs.append(e)

        for node in others:
            t = threading.Thread(target=run, args=(node,), daemon=True)
            t.start()
            threads.append(t)
        for t in threads:
            t.join()
        if errs:
            raise errs[0]

    # -- remote execution (executor.go:1000-1083) ----------------------------

    def _exec_remote(self, node: Node, index: str, query: Query,
                     slices: Optional[list[int]], opt: ExecOptions,
                     gens_out: Optional[list] = None) -> list:
        """``gens_out`` (hedged legs only) defers the response's
        generation tokens to the caller instead of letting the client
        apply them — the hedge race applies the WINNER's tokens only."""
        if self.client is None:
            raise SliceUnavailableError(
                f"no client to reach remote node {node.host}")
        ctx = opt.ctx
        kwargs = {}
        if gens_out is not None and getattr(self.client,
                                            "generation_aware", False):
            kwargs["gens_out"] = gens_out
        t0 = time.perf_counter()
        try:
            with sched_context.span(
                    "rpc", peer=node.host,
                    slices=len(slices) if slices else 0):
                if ctx is not None and getattr(self.client,
                                               "deadline_aware", False):
                    # The peer inherits the REMAINING budget (not the
                    # original) and the query id, so its leg registers
                    # under the same query and a cluster cancel finds
                    # it; the client clamps socket timeouts + its
                    # idempotent retry to the budget. Scripted test
                    # fakes without the marker keep the plain call
                    # shape.
                    ctx.check()
                    return self.client.execute_query(
                        node, index, str(query), slices, remote=True,
                        deadline_s=ctx.remaining(), query_id=ctx.id,
                        **kwargs)
                return self.client.execute_query(node, index,
                                                 str(query), slices,
                                                 remote=True, **kwargs)
        finally:
            obs_metrics.RPC_SECONDS.labels(
                peer=node.host, kind="query").observe(
                    time.perf_counter() - t0)

    def _apply_remote_gens(self, gens_list: list) -> None:
        """Apply a deferred (peer, payload) token list — the winning
        hedge leg's — to the coordinator generation map."""
        if self.gens is None:
            return
        for peer, payload in gens_list:
            self.gens.apply_wire(peer, payload)

    # -- map-reduce core (executor.go:1087-1236) -----------------------------

    def _slices_by_node(self, nodes: list[Node], index: str,
                        slices: list[int],
                        missing: Optional[list] = None
                        ) -> list[tuple[Node, list[int]]]:
        """Group ``slices`` by the replica owner that will serve each.
        With a fault manager attached, owners are consulted in health
        order with open circuits sunk to the end (fault subsystem) —
        so the first query after a peer dies pays one timeout, and
        every query after it routes around the open circuit without
        paying anything. ``missing`` (partial mode) collects slices
        with no owner among ``nodes`` instead of raising.

        Owners come from ``read_nodes`` — READ authority, which equals
        plain placement except during an elastic resize, where a
        stream target's incomplete copy must not serve. This is also
        the server-side fence: a remote leg asking a mid-migration
        target for a moving slice fails here, which is what lets the
        coordinator's double-read treat a successful target leg as
        proof the target considers itself authoritative."""
        fault = self.fault
        # Storage integrity: slices whose LOCAL fragments are
        # quarantined must not be served from this node — skipping
        # the local owner here IS the transparent read failover (the
        # remaining breaker-ordered owners serve; a peer's own
        # quarantine surfaces as its leg failing, which the generic
        # re-map routes around).
        q = getattr(self.holder, "quarantine", None)
        if q is not None and not len(q):
            q = None
        # Tiered storage: same skip for slices whose blob-tier
        # fragments cannot be fetched back (tier.manager blocked set)
        # — no local bytes exist to serve them.
        tier = getattr(self.holder, "tier", None)
        if tier is not None and not tier._blocked_slices:
            tier = None
        m: dict[int, tuple[Node, list[int]]] = {}
        # Placement ordering memo: PARTITION_N bounds the distinct
        # owner tuples, so a 256-slice query pays ≤16 order_nodes
        # calls (each is a sort + per-owner breaker/health consults)
        # instead of one per slice.
        order_memo: dict[tuple, list[Node]] = {}
        for slice in slices:
            owners = self.cluster.read_nodes(index, slice)
            if fault is not None and len(owners) > 1:
                key = tuple(id(n) for n in owners)
                ordered = order_memo.get(key)
                if ordered is None:
                    ordered = order_memo[key] = fault.order_nodes(
                        owners, local=self.host)
                owners = ordered
            for node in owners:
                if (q is not None and node.host == self.host
                        and q.slice_blocked(index, slice)):
                    ctx = sched_context.current()
                    if ctx is not None:
                        # Tail sampling: a corruption-driven failover
                        # is keep-worthy (obs.sampler "corruption").
                        ctx.note_flag("corruption")
                    continue
                if (tier is not None and node.host == self.host
                        and tier.slice_blocked(index, slice)):
                    continue
                if any(n is node for n in nodes):
                    m.setdefault(id(node), (node, []))[1].append(slice)
                    break
            else:
                if missing is not None:
                    missing.append(slice)
                    continue
                raise SliceUnavailableError(str(slice))
        return list(m.values())

    # -- elastic-resize double reads (cluster.resize) ------------------------

    def _resize_moving_groups(self, index: str, slices: list[int]):
        """``{(old_hosts, new_hosts): [slices]}`` for the slices of
        ``slices`` sitting in MIGRATING partitions of an in-flight
        resize, or None when there are none (the hot-path answer —
        one attr read when no resize is in flight)."""
        from .cluster.topology import RESIZE_MIGRATING
        cl = self.cluster
        if cl.resize is None:
            return None
        groups: dict[tuple, list[int]] = {}
        for s in slices:
            mv = cl.moving_slice(index, s)
            if mv is None or mv[0] != RESIZE_MIGRATING:
                continue
            groups.setdefault((mv[1], mv[2]), []).append(s)
        return groups or None

    def _double_read_side(self, hosts, index: str, c: Call,
                          slices: list[int], opt: ExecOptions,
                          map_fn, reduce_fn, local_fn,
                          gens_out: list):
        """One side of a double-read: try each candidate owner in turn
        (local legs compute in-process, remote legs forward with
        private token custody). Raises the last error when every
        candidate failed."""
        cl = self.cluster
        last: Optional[Exception] = None
        ordered = list(hosts)
        if self.fault is not None and len(ordered) > 1:
            ordered = sorted(
                ordered,
                key=lambda h: 0 if (h == self.host
                                    or self.fault.would_allow(h))
                else 1)
        for host in ordered:
            try:
                if host == self.host:
                    # The read-authority fence applies to the local leg
                    # exactly as _slices_by_node applies it to remote
                    # ones: a mid-migration target must not serve its
                    # incomplete copy, even to itself.
                    if not all(cl.read_allowed(host, index, s)
                               for s in slices):
                        raise SliceUnavailableError(
                            f"{host} not read-authoritative for"
                            f" {slices}")
                    with sched_context.use(opt.ctx):
                        if local_fn is not None:
                            r = local_fn(slices)
                            if r is not NotImplemented:
                                return r
                        return self._mapper_local(slices, map_fn,
                                                  reduce_fn)
                node = cl.node_by_host(host) or Node(host)
                rs = self._exec_remote(node, index, Query([c]), slices,
                                       opt, gens_out=gens_out)
                return rs[0] if rs else None
            except (QueryDeadlineError, QueryCancelledError):
                raise
            except Exception as e:  # noqa: BLE001 - next candidate
                last = e
        raise last if last is not None else SliceUnavailableError(
            str(slices))

    def _target_tokens_newest(self, index: str, slices: list[int],
                              gens_list: list) -> bool:
        """The double-read's newest-token-wins check: before the
        TARGET side's answer is accepted, its piggybacked (uid, gen)
        tokens must be at least as new as the map's freshest knowledge
        of each slice — a straggling or rolled-back target (same uid,
        LOWER generation than previously observed) can never win. A
        fresh uid (reopened fragment) reads as newest: its on-disk
        state is the durable acked state."""
        if self.gens is None:
            return True
        from .cluster import generations as gens_mod
        fresh: dict[int, dict] = {}
        peers: dict[int, str] = {}
        for peer, payload in gens_list:
            decoded = gens_mod.decode_wire(payload)
            if decoded is None:
                continue
            idx, tokens = decoded
            if idx != index:
                continue
            for s, toks in tokens.items():
                fresh[s] = toks
                peers[s] = peer
        for s in slices:
            toks = fresh.get(s)
            if toks is None:
                continue  # target reported nothing: nothing to refute
            known = self.gens.tokens(peers.get(s, ""), index, s,
                                     max_age_s=float("inf"))
            if not known:
                continue
            for fk, (uid, gen) in known.items():
                got = toks.get(fk)
                if got is not None and got[0] == uid and got[1] < gen:
                    return False
        return True

    def _exec_double_read(self, index: str, c: Call, slices: list[int],
                          old_hosts, new_hosts, opt: ExecOptions,
                          map_fn, reduce_fn, local_fn=None):
        """A moving slice group's fan-out during the MIGRATING phase
        of an elastic resize (docs/CLUSTER_RESIZE.md): both owner
        sides are queried concurrently —

        - the OLD side is authoritative pre-flip (its copy has every
          bit; the stream target's may not) and wins whenever it
          answers;
        - the NEW side can only answer after it has flipped (the
          read-authority fence in _slices_by_node makes a
          mid-migration target refuse the leg), so a successful target
          answer is proof the epoch advanced under this query — the
          exact window the double-read exists for. It wins only when
          the old side failed AND its piggybacked generation tokens
          are the newest the coordinator map has seen for every slice.

        Token custody follows the hedged-read discipline: each side
        collects privately; ONLY the winner's tokens merge into the
        coordinator map."""
        pool = self._pool("hedge")
        gens_old: list = []
        gens_new: list = []
        f_old = pool.submit(self._double_read_side, old_hosts, index,
                            c, slices, opt, map_fn, reduce_fn,
                            local_fn, gens_old)
        f_new = pool.submit(self._double_read_side, new_hosts, index,
                            c, slices, opt, map_fn, reduce_fn,
                            local_fn, gens_new)
        ctx = opt.ctx
        try:
            while True:
                if ctx is not None:
                    ctx.check()
                if f_old.done():
                    break
                wait([f_old], timeout=(self._CTX_POLL_S
                                       if ctx is not None else None))
        except BaseException:
            f_old.cancel()
            f_new.cancel()
            raise
        try:
            result = f_old.result()
        except (QueryDeadlineError, QueryCancelledError):
            f_new.cancel()
            raise
        except Exception as old_err:  # noqa: BLE001 - target may win
            try:
                while not f_new.done():
                    if ctx is not None:
                        ctx.check()
                    wait([f_new],
                         timeout=(self._CTX_POLL_S
                                  if ctx is not None else None))
                result = f_new.result()
            except (QueryDeadlineError, QueryCancelledError):
                raise
            except Exception:  # noqa: BLE001 - both sides dead
                raise old_err
            if not self._target_tokens_newest(index, slices, gens_new):
                raise old_err
            obs_metrics.RESIZE_DOUBLE_READS.labels("target").inc()
            self._apply_remote_gens(gens_new)
            return result
        obs_metrics.RESIZE_DOUBLE_READS.labels("source").inc()
        self._apply_remote_gens(gens_old)
        # The losing target leg is abandoned, not awaited: its socket
        # timeouts are budget-clamped and its tokens never merge.
        f_new.cancel()
        return result

    # Wake tick of the fan-out wait loop for lifecycle-bound queries:
    # bounds how long a cancellation or deadline expiry can go unseen
    # while every leg is still in flight.
    _CTX_POLL_S = 0.25
    # Grace given to in-flight legs of a DEAD (expired/cancelled)
    # query before abandoning them: each leg is ctx-checked per slice
    # and its remote socket timeouts are clamped to the (now exhausted)
    # budget, so abandoned legs self-terminate promptly — holding the
    # caller (and its admission slot) for a stalled peer would defeat
    # the deadline.
    _DEAD_DRAIN_S = 0.5

    def _map_reduce(self, index: str, slices: list[int], c: Call,
                    opt: ExecOptions, map_fn: Callable,
                    reduce_fn: Callable, local_fn: Callable = None):
        if not slices:
            return None
        if opt.remote:
            nodes = [self.cluster.node_by_host(self.host)]
        else:
            nodes = list(self.cluster.nodes)

        ctx = opt.ctx
        if ctx is not None:
            ctx.check()
            # Every slice leg re-checks the budget on entry, so an
            # expiry stops the per-slice map mid-fan-out instead of
            # draining the whole slice list.
            inner_map, inner_local = map_fn, local_fn

            def map_fn(slice, _m=inner_map):
                ctx.check()
                return _m(slice)

            if inner_local is not None:
                def local_fn(batch, _l=inner_local):
                    ctx.check()
                    return _l(batch)

        result = None
        processed = 0
        futures: dict = {}
        # Degraded reads (?partial=1): slices with no reachable
        # replica land here instead of failing the query; the handler
        # reports them as X-Pilosa-Partial.
        missing: Optional[list] = None
        if opt.partial:
            if opt.missing_slices is None:
                opt.missing_slices = []
            missing = opt.missing_slices

        first = True    # the next fan-out is the read's first

        def submit(nodes, slices, groups=None):
            nonlocal processed, first
            before = len(missing) if missing is not None else 0
            # Elastic resize, migrating phase: moving slices fan out as
            # DOUBLE-READ legs (old owner authoritative, new owner the
            # fenced fallback) instead of riding the normal grouping —
            # health ordering must never route a read to a target whose
            # copy is still streaming. The sentinel node None marks
            # these futures: their failover lives inside the leg, so
            # the outer re-map must not retry them.
            if not opt.remote and self.cluster.resize is not None:
                groups = self._resize_moving_groups(index, slices)
                if groups:
                    moved = set()
                    pool = self._pool("node")
                    for (old_hosts, new_hosts), group in groups.items():
                        moved.update(group)
                        fut = pool.submit(
                            self._exec_double_read, index, c, group,
                            old_hosts, new_hosts, opt, map_fn,
                            reduce_fn, local_fn)
                        futures[fut] = (None, group)
                        if ctx is not None:
                            ctx.add_leg("double-read", len(group))
                    slices = [s for s in slices if s not in moved]
            if groups is None or self.cluster.resize is not None:
                groups = self._slices_by_node(nodes, index, slices,
                                              missing=missing)
            # A read whose whole first fan-out is ONE leg on this node
            # runs it here, on the calling thread: the pool could only
            # make this thread wait (a Future, a queue put, two thread
            # wake-ups, twice the threads under one interpreter lock)
            # for work it can do itself. The leg comes back as the
            # finished future the loop below reads, so a failure takes
            # the same re-map, through the pool. Local legs running at
            # once are then bounded by admission (sched/admission.py),
            # not by the pool's workers. Deadline and cancel stay
            # cooperative (the checks inside the leg); what is given up
            # is walking away from a STALLED local leg. A pod's "local"
            # leg may fan out to its other processes over the network,
            # so a pod keeps the pool and the walk-away.
            inline = (first and len(groups) == 1
                      and groups[0][0].host == self.host
                      and self.cluster.resize is None
                      and self.pod is None)
            first = False
            self.legs["inline" if inline else "pooled"] += 1
            start = _ran_here if inline else self._pool("node").submit
            for node, node_slices in groups:
                fut = start(self._mapper_node, node, index, c,
                            node_slices, opt, map_fn, reduce_fn,
                            local_fn)
                futures[fut] = (node, node_slices)
                if ctx is not None:
                    ctx.add_leg(node.host, len(node_slices))
            if missing is not None:
                if ctx is not None and len(missing) > before:
                    # Tail sampling: a degraded (partial) answer is
                    # keep-worthy evidence (obs.sampler "partial").
                    ctx.note_flag("partial")
                # Unservable slices still count toward completion —
                # that is what "partial" means.
                processed += len(missing) - before

        # One span covers the whole fan-out INCLUDING the reduce/merge
        # of completed legs (per-leg detail comes from the leg/rpc
        # spans recorded inside _mapper_node).
        span = sched_context.span("map_reduce", call=c.name,
                                  slices=len(slices))
        span.__enter__()
        cost = ctx.cost if ctx is not None else None
        programs_before = cost.device_programs if cost is not None else 0
        cold_before = cost.cold_leaves if cost is not None else 0
        waits_before = cost.fill_waits if cost is not None else 0
        # The first fan-out of a whole-index read takes its grouping
        # from the route record; a failover re-map walks.
        route = (getattr(slices, "route", None) if not opt.remote
                 else None)
        try:
            submit(nodes, slices,
                   route["groups"] if route is not None else None)
            while processed < len(slices):
                # A leg that ran inline is finished already: it is read
                # without a wait, and without a ``legs_wait`` stage.
                done = [f for f in futures if f.done()]
                if done:
                    pass
                elif ctx is None:
                    done, _ = wait(list(futures),
                                   return_when=FIRST_COMPLETED)
                else:
                    # Deadline-driven cancellation: wake periodically
                    # so an expiry or DELETE-cancel interrupts the
                    # fan-out even while every leg is still running.
                    # The legs run on pool threads (their stages are
                    # this query's ``offThread``); this thread's wait
                    # for them is a stage of its own, so ``execute``
                    # stays executor time and not the legs' time.
                    ctx.check()
                    with ctx.stage("legs_wait"):
                        done, _ = wait(list(futures),
                                       timeout=self._CTX_POLL_S,
                                       return_when=FIRST_COMPLETED)
                for fut in done:
                    node, node_slices = futures.pop(fut)
                    try:
                        r = fut.result()
                    except (QueryDeadlineError, QueryCancelledError):
                        # The QUERY died, not the node: no replica
                        # re-map — surface it (handler maps to 504/409).
                        raise
                    except Exception as e:  # noqa: BLE001 - retry replicas
                        if node is None:
                            # A double-read leg already exhausted both
                            # sides of the migration (old owners AND
                            # the fenced new owner) — there is no
                            # further replica to re-map onto. Partial
                            # mode keeps its contract: the slices are
                            # reported missing instead of failing the
                            # query.
                            if missing is not None:
                                missing.extend(node_slices)
                                processed += len(node_slices)
                                if ctx is not None:
                                    ctx.note_flag("partial")
                                continue
                            raise
                        # Filter the failed node; re-map its slices onto
                        # surviving replicas (executor.go:1137-1151).
                        # The client already fed the failure into the
                        # breaker/health state, so the re-map's
                        # _slices_by_node consults an open circuit
                        # instead of rediscovering the failure — and
                        # the NEXT query skips the peer up front.
                        nodes = [n for n in nodes if n is not node]
                        obs_metrics.FAILOVER_SLICES.labels(
                            node.host or "local").inc(len(node_slices))
                        if ctx is not None:
                            # Tail sampling: a failover leg is keep-
                            # worthy evidence (obs.sampler "breaker").
                            ctx.note_flag("failover")
                        with sched_context.span(
                                "failover", peer=node.host,
                                slices=len(node_slices),
                                error=type(e).__name__):
                            pass
                        try:
                            submit(nodes, node_slices)
                        except SliceUnavailableError:
                            raise e
                        continue
                    with sched_context.stage(
                            "merge", host=(node.host if node is not None
                                           else "double-read")):
                        result = reduce_fn(result, r)
                    processed += len(node_slices)
        finally:
            if cost is not None and cost.device_programs > programs_before:
                # How wide a mesh this fan-out's device programs ran
                # on, how many of their operand slabs it filled and
                # how many it waited for another query to fill.
                span.tag(mesh_devices=cost.mesh_devices,
                         cold_leaves=cost.cold_leaves - cold_before,
                         fill_waits=cost.fill_waits - waits_before)
            span.__exit__(None, None, None)
            # On an error path, drain what we started: the pool is
            # shared with other queries, and the old per-query pool's
            # exit joined its legs — keep that (cancel what hasn't
            # started, wait out what has). A DEAD query's in-flight
            # legs get a bounded grace instead: they are cooperatively
            # cancelled (per-slice ctx checks, budget-clamped socket
            # timeouts) and waiting a stalled peer out here would hold
            # the executor slot past the deadline the caller paid for.
            pending = [f for f in futures if not f.cancel()]
            if pending:
                if ctx is not None and (ctx.cancelled()
                                        or ctx.expired()):
                    wait(pending, timeout=self._DEAD_DRAIN_S)
                else:
                    wait(pending)
        return result

    def _mapper_node(self, node: Node, index: str, c: Call,
                     slices: list[int], opt: ExecOptions, map_fn, reduce_fn,
                     local_fn=None):
        # Bind the query context to this worker thread so the device
        # dispatch layer (parallel.mesh) and nested pool legs reached
        # from here can check the budget without a ctx argument.
        with sched_context.use(opt.ctx):
            if opt.ctx is not None:
                opt.ctx.check()
            if node.host == self.host:
                with sched_context.stage("leg",
                                         host=node.host or "local",
                                         slices=len(slices)):
                    if local_fn is not None:
                        r = local_fn(slices)
                        if r is not NotImplemented:
                            return r
                    if (self.pod is not None and self.pod.is_coordinator
                            and not opt.pod_local):
                        return self._pod_host_mapper(index, c, slices,
                                                     opt, map_fn,
                                                     reduce_fn)
                    return self._mapper_local(slices, map_fn, reduce_fn)
            hedge_s = (self.fault.hedge_delay_s(node.host)
                       if self.fault is not None else None)
            if hedge_s:
                return self._exec_remote_hedged(node, index, c, slices,
                                                opt, map_fn, reduce_fn,
                                                hedge_s)
            results = self._exec_remote(node, index, Query([c]), slices,
                                        opt)
            return results[0] if results else None

    def _exec_remote_hedged(self, node: Node, index: str, c: Call,
                            slices: list[int], opt: ExecOptions,
                            map_fn, reduce_fn, hedge_s: float,
                            local_fn=None):
        """Tail-tolerant remote leg (fault subsystem, opt-in): fire the
        primary replica's RPC; if it hasn't answered within ``hedge_s``
        (max of the configured floor and the peer's p95-ish latency
        EWMA), fire the SAME slices at the surviving replica owners and
        take whichever side completes first — first-response-wins, the
        loser is cancelled if unstarted and abandoned otherwise (its
        socket timeout stays bounded by the query budget). Map-reduce
        legs are pure reads, so a duplicated leg is only spent work,
        never a double write. A hedge that loses the race is never
        re-raised; if BOTH sides fail the primary's error surfaces and
        the outer re-map takes over.

        Generation accounting: each side collects its piggybacked
        tokens privately and ONLY the winner's merge into the
        coordinator map — a loser that straggles in with older state
        (it started earlier, or served a stale replica) must never
        overwrite what the winner reported.

        ``local_fn(slices)`` overrides the local hedge leg's
        computation (the TopN pushdown's exact partial); the default
        runs the per-slice map/reduce."""
        pool = self._pool("hedge")
        query = Query([c])
        primary_gens: list = []

        def primary_leg():
            with sched_context.use(opt.ctx):    # the rpc span's query
                rs = self._exec_remote(node, index, query, slices, opt,
                                       gens_out=primary_gens)
            return rs[0] if rs else None

        primary = pool.submit(primary_leg)
        done, _ = wait([primary], timeout=hedge_s)
        if done:
            res = primary.result()
            self._apply_remote_gens(primary_gens)
            return res
        others = [n for n in self.cluster.nodes if n is not node]
        try:
            groups = self._slices_by_node(others, index, slices)
        except SliceUnavailableError:
            groups = []
        if not groups:
            res = primary.result()
            self._apply_remote_gens(primary_gens)
            return res
        obs_metrics.HEDGED_REQUESTS.labels("fired").inc()
        with sched_context.span("hedge", peer=node.host,
                                slices=len(slices)):
            pass
        hedge_gens: list = []

        def hedge_leg(n2: Node, sl: list[int]):
            if n2.host == self.host:
                with sched_context.use(opt.ctx):
                    if local_fn is not None:
                        return local_fn(sl)
                    return self._mapper_local(sl, map_fn, reduce_fn)
            with sched_context.use(opt.ctx):
                rs = self._exec_remote(n2, index, query, sl, opt,
                                       gens_out=hedge_gens)
            return rs[0] if rs else None

        hedges = [pool.submit(hedge_leg, n2, sl) for n2, sl in groups]
        ctx = opt.ctx
        primary_err = hedge_err = None
        primary_res = hedge_res = None
        primary_done = hedge_done = False
        while True:
            if ctx is not None:
                ctx.check()
            # Consume completed sides BEFORE blocking: a hedge that
            # finished while we were submitting must win immediately,
            # not after the slow primary finally returns.
            if not primary_done and primary.done():
                primary_done = True
                try:
                    primary_res = primary.result()
                except (QueryDeadlineError, QueryCancelledError):
                    raise
                except Exception as e:  # noqa: BLE001 - hedges cover
                    primary_err = e
            if not hedge_done and all(f.done() for f in hedges):
                hedge_done = True
                try:
                    r = None
                    for f in hedges:
                        r = reduce_fn(r, f.result())
                    hedge_res = r
                except (QueryDeadlineError, QueryCancelledError):
                    raise
                except Exception as e:  # noqa: BLE001 - primary covers
                    hedge_err = e
            if primary_done and primary_err is None:
                obs_metrics.HEDGED_REQUESTS.labels("primary_won").inc()
                for f in hedges:
                    f.cancel()
                self._apply_remote_gens(primary_gens)
                return primary_res
            if hedge_done and hedge_err is None:
                obs_metrics.HEDGED_REQUESTS.labels("hedge_won").inc()
                primary.cancel()
                self._apply_remote_gens(hedge_gens)
                return hedge_res
            if primary_done and hedge_done:
                raise primary_err
            wait([f for f in [primary, *hedges] if not f.done()],
                 timeout=self._CTX_POLL_S if ctx is not None else None,
                 return_when=FIRST_COMPLETED)

    def _pod_host_mapper(self, index: str, c: Call, slices: list[int],
                         opt: ExecOptions, map_fn, reduce_fn):
        """Pod-internal host-path fan-out: this pod's "local" slices are
        spread over its processes, so partition by owner process — owned
        slices run the plain local path, the rest go to the owning pod
        process as podLocal HTTP legs (parallel.pod placement)."""
        by_pid: dict[int, list[int]] = {}
        for s in slices:
            by_pid.setdefault(self.pod.owner_pid(s), []).append(s)
        result = None
        pool = self._pool("pod")
        futs = []
        for pid, group in by_pid.items():
            if pid == self.pod.pid:
                futs.append(pool.submit(self._mapper_local, group,
                                        map_fn, reduce_fn))
            else:
                futs.append(pool.submit(self._exec_pod_remote, pid,
                                        index, c, group, opt.ctx))
        try:
            for fut in futs:
                result = reduce_fn(result, fut.result())
        finally:
            # Shared pool: a failed leg must not abandon its siblings
            # mid-flight (the caller may re-map these slices onto
            # replicas — an abandoned leg would execute them twice).
            pending = [f for f in futs if not f.cancel()]
            if pending:
                wait(pending)
        return result

    def _exec_pod_remote(self, pid: int, index: str, c: Call,
                         slices: list[int], ctx=None):
        if self.client is None:
            raise SliceUnavailableError(
                f"no client to reach pod process {pid}")
        kwargs = {}
        if ctx is not None and getattr(self.client, "deadline_aware",
                                       False):
            ctx.check()
            kwargs = {"deadline_s": ctx.remaining(), "query_id": ctx.id}
        results = self.client.execute_query(
            Node(self.pod.peers[pid]), index, str(Query([c])), slices,
            remote=True, pod_local=True, **kwargs)
        return results[0] if results else None

    def _mapper_local(self, slices: list[int], map_fn, reduce_fn):
        # Goroutine-per-slice equivalent (executor.go:1201-1236); the numpy
        # and device work inside map_fn releases the GIL. Wide fan-outs
        # chunk several slices per pool task and pre-reduce inside the
        # task: at 256 slices the per-task submit/schedule overhead was
        # a third of the whole query, and reduce order is already
        # arbitrary (the cluster layer reduces in completion order).
        if len(slices) == 1:
            return reduce_fn(None, map_fn(slices[0]))
        pool = self._pool("slice")
        # Propagate the calling thread's query context into the nested
        # slice-pool legs: the container algebra (map AND the in-group
        # pre-reduce) actually runs THERE, and without the binding its
        # per-query attribution (cost ledger, spans, profiler query
        # tags) silently lands nowhere.
        ctx = sched_context.current()
        chunk = max(1, len(slices) // (4 * self.max_workers))

        def run_group(group: list[int]):
            # One binding covers the whole group — map legs and the
            # pre-reduce merges between them.
            with sched_context.use(ctx):
                r = None
                for s in group:
                    r = reduce_fn(r, map_fn(s))
                return r

        if chunk == 1:
            # Narrow fan-out: submit per slice — a single-slice group
            # would pay one extra reduce_fn pass per slice for nothing.
            if ctx is None:
                futs = [pool.submit(map_fn, s) for s in slices]
            else:
                def one(s, _ctx=ctx):
                    with sched_context.use(_ctx):
                        return map_fn(s)
                futs = [pool.submit(one, s) for s in slices]
        else:
            futs = [pool.submit(run_group, slices[i:i + chunk])
                    for i in range(0, len(slices), chunk)]
        result = None
        try:
            for fut in futs:
                result = reduce_fn(result, fut.result())
        finally:
            # Shared pool: if map_fn or reduce_fn raised, don't abandon
            # in-flight legs — the caller re-maps these slices onto a
            # replica, and an abandoned leg would run them twice while
            # occupying pool slots (same drain as _mapper/_mapper_pod).
            pending = [f for f in futs if not f.cancel()]
            if pending:
                wait(pending)
        return result
