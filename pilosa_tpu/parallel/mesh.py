"""Device-mesh slice executor: mapReduce as SPMD collectives.

The reference fans a query out goroutine-per-slice and per-node, then
reduces associatively — sum for Count, pair-merge for TopN
(executor.go:1103-1236). On TPU the slice axis IS a mesh axis: packed
slice blocks are sharded over devices with `jax.sharding`, the per-slice
map is the sharded computation inside `shard_map`, and the reduce is an
XLA collective riding ICI — `psum` for Count, `psum` of per-row counts +
`top_k` for TopN — instead of an HTTP/gossip merge.

Axis conventions:
- ``slices``: the column-slice axis (data-parallel; the reference's unit
  of placement, cluster.go:198-240). Count/TopN reduce over it.
- ``rows``: candidate-row axis for TopN blocks (tensor-parallel
  analogue); per-row counts are psum'd over ``slices``, gathered over
  ``rows`` for the final top-k.

Program forms: every query program is built by the shape-stable
**global-view catalogue** in ``parallel.programs`` — plain ``jax.jit``
over globally sharded arrays with explicit ``NamedSharding`` placement,
slice axes padded to canonical buckets (``programs.slice_bucket``) so
the compile count is bucket-bound instead of scaling with slice count,
and the final Count/TopN reduction is an in-program all-reduce. This
module holds the dispatch entry points, the expression bodies those
programs share, and the compile-accounting wrapper
(``_finalize_program``). The ``shard_map`` builders left here are the
sparse-upload ``densify`` (a Pallas kernel is a per-shard primitive)
and the fixed-shape ``count_op`` / ``topn_counts`` / ``query_step``.
"""

from __future__ import annotations

import functools
import heapq
import logging
import os
import threading
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..fault import failpoints as _failpoints
from ..obs import accounting as _accounting
from ..ops.kernels import _BITWISE
from ..sched import context as sched_context

AXIS_SLICES = "slices"
AXIS_ROWS = "rows"


def _dispatch_gate() -> None:
    """Every device-dispatch entry point passes here before compiling
    or dispatching a program: the query-budget check (sched) plus the
    ``mesh.dispatch`` failpoint (fault) — an injected FailpointError
    is an OSError, so the executor's device-trouble handlers fall back
    to the host path exactly as they would for a real backend fault."""
    sched_context.check_current()
    if _failpoints.ACTIVE is not None:
        _failpoints.ACTIVE.hit("mesh.dispatch")


# -- per-tenant device-queue fairness ----------------------------------------
# Admission (sched.admission) strides tenants at the HTTP front door,
# but ONE admitted query fans out many device dispatches; below
# admission every dispatch raced FIFO for the backend, so a wide
# tenant's fan-out could monopolize the device queue against a quiet
# tenant's single program. The FairDispatchQueue closes that gap: a
# bounded slot pool at the dispatch boundary where, under contention,
# waiters are admitted in stride order over their tenants' effective
# admission weights (the same penalty-boxed weights sched.tenants
# computes — one fairness currency at both levels). Uncontended cost
# is one lock acquire; the queue is installed only when the server
# runs with tenants (install_fair_dispatch), and PILOSA_MESH_FAIR=0
# removes it entirely.

class FairDispatchQueue:
    """Stride-scheduled slot pool for device dispatches.

    Each tenant carries a virtual ``pass``; enqueueing advances it by
    ``1/weight`` and waiters wake lowest-pass-first, so over any
    contended window tenants hold slots in proportion to their
    weights regardless of how many dispatches each has queued. A new
    (or long-idle) tenant starts at the global pass frontier — it
    cannot bank credit, only compete fairly from now on."""

    def __init__(self, slots: int, weight_fn=None):
        self.slots = max(1, int(slots))
        self.weight_fn = weight_fn
        self._mu = threading.Lock()
        self._in_flight = 0
        # Heap entries are [pass, seq, Event, cancelled]; list order
        # compares (pass, seq) — seq is unique, the Event never
        # participates. ``cancelled`` marks a waiter that gave up
        # (query killed while queued); release() skips it.
        self._heap: list[list] = []
        self._seq = 0
        self._tenant_pass: dict[str, float] = {}
        self._global_pass = 0.0
        self._dispatches = 0
        self._waits = 0

    def _stride(self, tenant: str) -> float:
        weight = 1.0
        fn = self.weight_fn
        if fn is not None:
            try:
                weight = float(fn(tenant))
            except Exception:  # noqa: BLE001 - fairness is advisory
                weight = 1.0
        return 1.0 / max(weight, 1e-3)

    def acquire(self, tenant: str) -> None:
        with self._mu:
            self._dispatches += 1
            if self._in_flight < self.slots and not self._heap:
                self._in_flight += 1
                return
            self._waits += 1
            p = max(self._tenant_pass.get(tenant, 0.0),
                    self._global_pass) + self._stride(tenant)
            self._tenant_pass[tenant] = p
            self._seq += 1
            entry = [p, self._seq, threading.Event(), False]
            heapq.heappush(self._heap, entry)
        ev = entry[2]
        while not ev.wait(0.05):
            # Keep the query's cancellation/kill/deadline checks live
            # while queued — a killed query must not occupy the queue.
            try:
                sched_context.check_current()
            except BaseException:
                with self._mu:
                    if not ev.is_set():
                        entry[3] = True
                        raise
                # Woken concurrently with the cancel: we own a slot —
                # hand it on before propagating.
                self.release()
                raise

    def release(self) -> None:
        with self._mu:
            while self._heap:
                _p, _seq, ev, cancelled = heapq.heappop(self._heap)
                if cancelled:
                    continue
                self._global_pass = _p
                ev.set()  # slot transfers: _in_flight is unchanged
                return
            self._in_flight -= 1

    def state(self) -> dict:
        with self._mu:
            return {"slots": self.slots,
                    "inFlight": self._in_flight,
                    "queued": sum(1 for e in self._heap if not e[3]),
                    "dispatches": self._dispatches,
                    "waits": self._waits}


_FAIR: "FairDispatchQueue | None" = None
_FAIR_DEPTH = threading.local()
DEFAULT_FAIR_SLOTS = 8


def install_fair_dispatch(weight_fn=None, slots: int = 0) -> None:
    """Arm per-tenant dispatch fairness (server.open, once tenants
    exist). ``weight_fn(tenant) -> float`` is typically
    ``TenantRegistry.effective_weight``. PILOSA_MESH_FAIR=0 vetoes
    (the escape hatch when a deployment wants raw FIFO dispatch);
    PILOSA_MESH_FAIR_SLOTS overrides the slot count."""
    global _FAIR
    if os.environ.get("PILOSA_MESH_FAIR", "") == "0":
        _FAIR = None
        return
    if not slots:
        try:
            slots = int(os.environ.get("PILOSA_MESH_FAIR_SLOTS", "")
                        or DEFAULT_FAIR_SLOTS)
        except ValueError:
            slots = DEFAULT_FAIR_SLOTS
    _FAIR = FairDispatchQueue(slots, weight_fn)


def uninstall_fair_dispatch() -> None:
    global _FAIR
    _FAIR = None


def fair_dispatch_state() -> "dict | None":
    q = _FAIR
    return q.state() if q is not None else None


def _fair_dispatch(fn):
    """Entry-point wrapper: hold one fair slot for the duration of the
    dispatch call. Reentrant per thread (an entry point called from
    inside another shares the outer slot), and a straight pass-through
    until install_fair_dispatch arms it."""
    @functools.wraps(fn)
    def gated(*args, **kwargs):
        q = _FAIR
        if q is None or getattr(_FAIR_DEPTH, "d", 0):
            return fn(*args, **kwargs)
        ctx = sched_context.current()
        tenant = (getattr(ctx, "tenant", "") or "") if ctx else ""
        q.acquire(tenant or "default")
        _FAIR_DEPTH.d = 1
        try:
            return fn(*args, **kwargs)
        finally:
            _FAIR_DEPTH.d = 0
            q.release()
    return gated


# -- compile-cache observability ---------------------------------------------
# Every serving program is built by an lru_cache'd builder below; a
# builder RUN is a compile-cache miss, and the program's FIRST
# invocation pays the XLA trace+compile. Both are counted here (plus
# the wall seconds of those first calls) so "is the cache hitting, and
# does anything warm it" is answerable from /status and /metrics
# instead of a stopwatch.

_COMPILE_MU = threading.Lock()
_COMPILE_STATS = {"programsBuilt": 0, "firstCalls": 0,
                  "compileSeconds": 0.0,
                  # Persistent on-disk cache outcomes (jax monitoring
                  # events, counted once arm_compile_cache registers
                  # the listener): a restarted process whose programs
                  # load from disk shows HITS here — the direct answer
                  # to "did the cache survive the restart".
                  "persistentHits": 0, "persistentMisses": 0}


def _on_jax_cache_event(event: str, **kwargs) -> None:
    if event.endswith("/cache_hits"):
        with _COMPILE_MU:
            _COMPILE_STATS["persistentHits"] += 1
    elif event.endswith("/cache_misses"):
        with _COMPILE_MU:
            _COMPILE_STATS["persistentMisses"] += 1


# The last compilations, behind /debug/vars ``compileLog``: which
# program (by its stable name), over which argument shapes, how long,
# and when — so a compile counted inside a serving window can be named.
_COMPILE_LOG: deque = deque(maxlen=16)


def compile_log() -> list[dict]:
    with _COMPILE_MU:
        return list(_COMPILE_LOG)


def _finalize_program(fn, name: str, **jit_kw):
    """Builder epilogue: ``jax.jit`` under a stable name, plus compile
    accounting.

    ``fn`` is the program's Python function (or its ``shard_map``).
    The name becomes the function's ``__name__`` BEFORE the jit, so the
    XLA module is ``jit_<name>`` (``count_exprs_k3``, ``topn_exact``,
    ``bsi_range``, …) in the profiler's ``XLA Modules`` line and in
    ``compileLog`` instead of ``jit_fn`` for every program.

    Accounting is per XLA COMPILATION, not per builder run: a jitted
    program re-traces for every distinct input shape, so before the
    bucket-stable catalogue a program serving 8, 12, 16... slices paid
    (and hid) one compile per slice count. The wrapper detects a
    compile by the jitted cache growing across the call
    (``_cache_size``) and charges its wall time to ``firstCalls`` /
    ``compileSeconds`` — making "compile count stays bucket-bound as
    slice count grows" an assertable number. The predicted first call
    runs under the ``compile`` stage of the query that triggers it."""
    fn.__name__ = fn.__qualname__ = name
    fn = jax.jit(fn, **jit_kw)
    with _COMPILE_MU:
        _COMPILE_STATS["programsBuilt"] += 1
    state = {"first": True}

    @functools.wraps(fn)
    def program(*args, **kwargs):
        first = state["first"]
        pre = fn._cache_size()
        t0 = time.perf_counter()
        if first:
            state["first"] = False  # benign race: double-count at worst
            with sched_context.stage("compile"):
                out = fn(*args, **kwargs)
        else:
            out = fn(*args, **kwargs)
        dt = time.perf_counter() - t0
        if fn._cache_size() > pre:
            with _COMPILE_MU:
                _COMPILE_STATS["firstCalls"] += 1
                _COMPILE_STATS["compileSeconds"] += dt
                _COMPILE_LOG.append({
                    "program": name,
                    "shapes": [list(getattr(a, "shape", ()))
                               for a in args],
                    "seconds": round(dt, 4), "at": time.time()})
            # Attribute the trace+compile to the query that paid it
            # (obs.accounting: compileMs in its cost ledger).
            _accounting.note_compile(dt)
        return out

    return program


def _run(fn, *args) -> np.ndarray:
    """One device program, in two statements so two stages can tell
    them apart: ``dispatch`` is the call into the jitted program, which
    returns before the device finishes; ``fetch`` is the ``np.asarray``
    of its result, which blocks until the device is done."""
    with sched_context.stage("dispatch"):
        out = fn(*args)
    with sched_context.stage("fetch"):
        return np.asarray(out)


def _run_hilo(fn, *args) -> list[int]:
    """``_run`` of a stacked (hi, lo) program, decoded under ``merge``."""
    arr = _run(fn, *args)
    with sched_context.stage("merge"):
        return hilo_combine(arr)


# Device programs dispatched through the entry points below, whoever
# asked (a query, the warm-up): /debug/vars ``mesh.programsRun``. A
# plain int under the GIL: a rare lost count is accepted, as in the
# cost ledger.
_PROGRAMS_RUN = 0


def programs_run() -> int:
    return _PROGRAMS_RUN


def _note_dispatch(mesh: Mesh, *operands) -> None:
    """Count one device-program dispatch on ``mesh``, and charge it
    (+ its operand bytes, and the mesh's width: how many devices the
    program ran on) to the current query's cost ledger
    (obs.accounting) — the per-query form of the dispatch stage.
    None-cost fast path: one thread-local read."""
    global _PROGRAMS_RUN
    _PROGRAMS_RUN += 1
    cost = _accounting.current_cost()
    if cost is not None:
        cost.note_device_dispatch(
            sum(int(getattr(a, "nbytes", 0)) for a in operands),
            mesh.devices.size)


def _all_program_caches():
    """Every lru_cache'd builder across the shard_map forms here AND
    the global-view catalogue (parallel.programs) — resolved lazily so
    either module can import first."""
    caches = list(_PROGRAM_CACHES)
    try:
        from . import programs as programs_mod
        caches.extend(programs_mod.PROGRAM_CACHES)
    except (ImportError, AttributeError):
        pass  # partial init during circular import: mesh's own caches
    return caches


def compile_stats() -> dict:
    """Aggregate XLA program-cache counters: lookup hits/misses over
    every lru_cache'd builder, live program count, the first-call
    compile totals, and the armed persistent-cache directory (None
    until arm_compile_cache has run)."""
    hits = misses = programs = 0
    for cache in _all_program_caches():
        info = cache.cache_info()
        hits += info.hits
        misses += info.misses
        programs += info.currsize
    with _COMPILE_MU:
        stats = dict(_COMPILE_STATS)
    stats["compileSeconds"] = round(stats["compileSeconds"], 3)
    return {"hits": hits, "misses": misses, "programs": programs,
            "persistentCacheDir": _compile_cache_dir,
            **stats}


def _rows_popcount(expr, leaves):
    """Per-slice-row int32 counts of ``expr`` over ``leaves`` [L, S, W]."""
    words = _eval_expr(expr, leaves)
    pc = jax.lax.population_count(words).astype(jnp.int32)
    return jnp.sum(pc, axis=-1)


_compile_cache_armed = False
_compile_cache_dir: str | None = None


def arm_compile_cache() -> str | None:
    """Point JAX's persistent compilation cache at its one directory
    before first device use, so a RESTARTED process reuses on-disk
    compiled programs instead of re-paying the trace+compile.

    One rule: where ``JAX_COMPILATION_CACHE_DIR`` is set jax already
    has the directory and nothing is set here; otherwise the cache is
    ``utils.cache_dir("xla")`` — one fixed, git-ignored directory
    inside the checkout. The path is part of the cache's key, so it is
    never derived from a data dir, temp name, pid or time. First call
    wins (jax.config is process-global); returns the directory, or
    None when the default cannot be created (a read-only install):
    the process then compiles everything itself, says so on the log,
    and ``compile_stats()["persistentCacheDir"]`` is null."""
    global _compile_cache_armed, _compile_cache_dir
    if _compile_cache_armed:
        return _compile_cache_dir
    _compile_cache_armed = True
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        from ..utils import cache_dir
        path = cache_dir("xla")
        try:
            os.makedirs(path, exist_ok=True)
        except OSError as e:
            logging.getLogger("pilosa_tpu.mesh").warning(
                "no persistent compile cache: cannot create %s (%s);"
                " set JAX_COMPILATION_CACHE_DIR to a writable"
                " directory", path, e)
            _compile_cache_dir = None
            return None
        jax.config.update("jax_compilation_cache_dir", path)
    # The serving programs are small: many compile in under jax's 1 s
    # default floor for writing an entry.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    _compile_cache_dir = path
    # Count on-disk cache outcomes (hit = a compile served from disk)
    # into compile_stats — the observable that proves a second process
    # reused the first one's compilations.
    from jax._src import monitoring as _jax_monitoring
    if _on_jax_cache_event not in _jax_monitoring.get_event_listeners():
        _jax_monitoring.register_event_listener(_on_jax_cache_event)
    return _compile_cache_dir


def make_mesh(n_devices: int | None = None, rows: int = 1) -> Mesh:
    """A (rows × slices) device mesh. ``rows=1`` gives the common 1-D
    slice mesh; TopN row-sharding uses rows>1."""
    arm_compile_cache()
    devs = jax.devices()
    n = n_devices or len(devs)
    if n > len(devs):
        raise ValueError(f"need {n} devices, have {len(devs)}")
    if n % rows:
        raise ValueError("n_devices must be divisible by rows")
    grid = np.array(devs[:n]).reshape(rows, n // rows)
    return Mesh(grid, (AXIS_ROWS, AXIS_SLICES))


def _slice_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(AXIS_SLICES))


def shard_slices(mesh: Mesh, arr: np.ndarray) -> jax.Array:
    """Place ``[n_slices, ...]`` on the mesh, sharded over the slice axis.
    n_slices must divide evenly (pad with zero slices host-side)."""
    return jax.device_put(arr, _slice_sharding(mesh))


def densify_mode() -> str | None:
    """Sparse-upload dispatch: "compiled" on real TPU (the Pallas
    densify kernel has no compiled form elsewhere), "interpret" when
    forced for CPU tests (PILOSA_TPU_SPARSE_UPLOAD=interpret), None =
    dense uploads only (=0, or non-TPU backends)."""
    v = os.environ.get("PILOSA_TPU_SPARSE_UPLOAD", "auto")
    if v == "0":
        return None
    if v == "interpret":
        return "interpret"
    return "compiled" if jax.devices()[0].platform == "tpu" else None


# The bucket widths the gate of ``ops.packed.pack_slab`` lets through (a
# power of two, at most 32): one densify program each, for every slab
# shape.
DENSIFY_WIDTHS = (1, 2, 4, 8, 16, 32)

# Told ``(mesh, lead_shape, subs, interpret)`` by every sparse upload,
# on the filling thread. Which of a slab shape's widths a fill meets
# depends on the row's data, not on the query's shape, so traffic
# cannot be counted on to compile them all early: the warm-up lane
# (sched.warmup) installs itself here and compiles a new shape's other
# widths off the serving threads. None: nobody listens.
on_densify = None


@functools.lru_cache(maxsize=64)
def _densify_sharded_fn(mesh: Mesh, lead_shape: tuple, subs: int,
                        g_slots: int, interpret: bool):
    from ..ops import pallas_kernels as pk
    n_words = subs * 128

    def per_shard(lanes, vals):  # [..., subs, G] slice-sharded axis 0
        flat_l = lanes.reshape((-1, subs, g_slots))
        flat_v = vals.reshape((-1, subs, g_slots))
        out = pk.densify_pallas(flat_l, flat_v, n_words, interpret)
        return out.reshape(lanes.shape[:-2] + (n_words,))

    # The output names its sharding as ``shard_slices`` does: on a
    # one-device mesh jit would otherwise hand back ``P()``, unequal to
    # a device_put slab's ``P(slices)``, and every program re-specialised
    # for each mix of slab origins among its leaves (2^k a k-leaf Count,
    # ~2 ms each, most of compiles_in_window).
    return _finalize_program(jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(AXIS_SLICES), P(AXIS_SLICES)),
        out_specs=P(AXIS_SLICES), check_vma=False), "densify",
        out_shardings=_slice_sharding(mesh))


@_fair_dispatch
def densify_sharded(mesh: Mesh, lanes: np.ndarray, vals: np.ndarray,
                    interpret: bool = False) -> jax.Array:
    """Upload bucketed sparse rows (ops.packed.pack_slab) and
    densify per shard: ``[S, (R,) subs, G]`` → slice-sharded
    ``[S, (R,) subs*128]`` dense words. The cold-path replacement for
    packing dense host-side and shipping 4 bytes per word, set or
    not."""
    _dispatch_gate()
    heard = on_densify
    if heard is not None:
        heard((mesh, lanes.shape[:-2], lanes.shape[-2], interpret))
    dl = shard_slices(mesh, lanes)
    dv = shard_slices(mesh, vals)
    fn = _densify_sharded_fn(mesh, lanes.shape[:-2], lanes.shape[-2],
                             lanes.shape[-1], interpret)
    return fn(dl, dv)


def warm_densify(mesh: Mesh, lead_shape: tuple, subs: int,
                 interpret: bool = False) -> None:
    """Run the densify program of every width once, on zeros, for one
    slab shape ``lead_shape + (subs * 128,)``: after it a first sparse
    fill of that shape compiles nothing, whatever its row's width."""
    for g_slots in DENSIFY_WIDTHS:
        zeros = np.zeros(lead_shape + (subs, g_slots), dtype=np.uint32)
        densify_sharded(mesh, zeros, zeros,
                        interpret=interpret).block_until_ready()


def pad_to_multiple(arr: np.ndarray, n: int) -> np.ndarray:
    """Pad axis 0 with zero slices to a multiple of n (zero slices are
    identity for every count/TopN reduction)."""
    rem = arr.shape[0] % n
    if rem == 0:
        return arr
    pad = [(0, n - rem)] + [(0, 0)] * (arr.ndim - 1)
    return np.pad(arr, pad)


@functools.lru_cache(maxsize=None)
def _count_fn(mesh: Mesh, op: str):
    """[S, W] × [S, W] → scalar total count, psum over the slice axis.

    Per-shard totals are split into 16-bit halves (int64 is off by
    default; a 1 B-column slab overflows int32) and recombined host-side.
    """
    bitwise = _BITWISE[op]

    def per_shard(a, b):  # a, b: [S/n, W]
        pc = jax.lax.population_count(bitwise(a, b)).astype(jnp.int32)
        row = jnp.sum(pc, axis=-1).ravel()  # ≤ 2^15 counts of ≤ 2^20 each
        hi = jax.lax.psum(jnp.sum(row >> 16), AXIS_SLICES)
        lo = jax.lax.psum(jnp.sum(row & 0xFFFF), AXIS_SLICES)
        return jnp.stack([hi, lo])  # one output = one host fetch

    return _finalize_program(jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(AXIS_SLICES), P(AXIS_SLICES)),
        out_specs=P()), f"count_{op}")


def count_op(mesh: Mesh, op: str, a: jax.Array, b: jax.Array) -> int:
    """Count(op(a, b)) over slice-sharded packed blocks — the mesh form of
    the executor's Count mapReduce (executor.go:568-597).

    Limited to 2^15 total slice-rows: the psum'd 16-bit lo half overflows
    int32 past that (same bound as kernels.op_count_total) — callers
    chunk the slice axis above it.
    """
    if a.ndim > 1 and a.shape[0] > (1 << 15):
        raise ValueError("count_op: more than 2^15 slice-rows per call")
    hilo = np.asarray(_count_fn(mesh, op)(a, b))
    return (int(hilo[0]) << 16) + int(hilo[1])


def _exprs_hi_lo(exprs, leaves):
    """Per-expression (hi, lo) 16-bit count halves over one leaf block
    [L, S, W] — each expression reads only ITS leaves (no redundant
    HBM traffic). Shared body of the count programs."""
    his, los = [], []
    n = leaves.shape[0]
    for expr in exprs:
        ids = expr_leaf_ids(expr)
        if ids == list(range(n)):
            sub, local = leaves, expr  # common case: uses every leaf
        else:
            sub = leaves[jnp.asarray(ids)]
            local = remap_expr_leaves(
                expr, {g: li for li, g in enumerate(ids)})
        row = _rows_popcount(local, sub).ravel()
        his.append(jnp.sum(row >> 16))
        los.append(jnp.sum(row & 0xFFFF))
    return jnp.stack(his), jnp.stack(los)


def slice_chunk_bound(n_dev: int) -> int:
    """Max slice-rows per psum'd program: the 16-bit lo halves sum to at
    most ``rows × 0xFFFF``, which must stay under int32 — 2^15 rows is
    the bound, and padding to the device multiple must not cross it."""
    return (1 << 15) - n_dev


@_fair_dispatch
def count_expr(mesh: Mesh, expr: tuple, leaves: np.ndarray) -> int:
    """Count the bitmap expression over slice-sharded leaf blocks.

    ``leaves`` is ``[n_leaves, n_slices, n_words]`` u32; slices are
    padded to the canonical bucket (programs.slice_bucket — zero slices
    are the count identity, and bucket-stable shapes keep the compile
    count bucket-bound) and chunked at the hi/lo int32 bound, so any
    slice count works.
    """
    _dispatch_gate()
    from . import programs as programs_mod
    n_dev = mesh.shape[AXIS_SLICES]
    fn = programs_mod.count_exprs_block_program(mesh, (expr,))
    total = 0
    step = slice_chunk_bound(n_dev)
    for off in range(0, leaves.shape[1], step):
        chunk = programs_mod.bucket_pad(
            leaves[:, off:off + step], 1, n_dev)
        # Per chunk: each loop pass dispatches one program.
        _note_dispatch(mesh, chunk)
        with sched_context.stage("upload"):
            block = shard_slices_axis1(mesh, chunk)
        total += _run_hilo(fn, block)[0]
    return total


def expr_leaf_ids(expr) -> list[int]:
    """Ordered unique leaf ids referenced by an expr tree (iterative —
    wide folds are ~leaf-count deep)."""
    seen: list[int] = []
    stack = [expr]
    while stack:
        node = stack.pop()
        if node[0] == "leaf":
            if node[1] not in seen:
                seen.append(node[1])
        else:
            stack.append(node[2])
            stack.append(node[1])
    return seen


def remap_expr_leaves(expr, remap: dict[int, int]) -> tuple:
    """Rebuild an expr tree with leaf ids remapped (iterative)."""
    done: dict[int, tuple] = {}
    stack = [expr]
    while stack:
        node = stack[-1]
        if node[0] == "leaf":
            done[id(node)] = ("leaf", remap[node[1]])
            stack.pop()
            continue
        left, right = node[1], node[2]
        if id(left) in done and id(right) in done:
            done[id(node)] = (node[0], done[id(left)], done[id(right)])
            stack.pop()
        else:
            if id(right) not in done:
                stack.append(right)
            if id(left) not in done:
                stack.append(left)
    return done[id(expr)]


@_fair_dispatch
def count_exprs_sharded(mesh: Mesh, exprs: tuple,
                        leaf_arrays: list[jax.Array]) -> list[int]:
    """K expression counts in ONE compiled program over shared
    device-resident leaf slabs — a PQL query carrying several Count
    calls pays one dispatch (and one host sync) instead of K.
    The reference executes calls strictly sequentially
    (executor.go:135-142); the counts are independent, so fusing them
    is observationally identical. Same bounds as count_expr_sharded.
    """
    _dispatch_gate()
    if leaf_arrays[0].shape[0] > slice_chunk_bound(
            mesh.shape[AXIS_SLICES]):
        raise ValueError("count_exprs_sharded: slice count above the"
                         " int32 hi/lo bound")
    from . import programs as programs_mod
    fn = programs_mod.count_exprs_program(mesh, exprs, len(leaf_arrays))
    _note_dispatch(mesh, *leaf_arrays)
    return _run_hilo(fn, *leaf_arrays)


def count_expr_sharded(mesh: Mesh, expr: tuple,
                       leaf_arrays: list[jax.Array]) -> int:
    """Count over per-leaf DEVICE-resident [n_slices, n_words] slabs
    (each sharded over the slice axis, e.g. from the residency cache —
    no host pack or upload on this path). All slabs must share one
    shape with n_slices ≤ slice_chunk_bound; leaves stack on device
    inside the compiled program. The K=1 form of count_exprs_sharded.
    """
    return count_exprs_sharded(mesh, (expr,), leaf_arrays)[0]


@_fair_dispatch
def fused_tree_sharded(mesh: Mesh, count_exprs: tuple,
                       topn_items: list[tuple],
                       leaf_arrays: list[jax.Array],
                       rows_arrays: list[jax.Array]
                       ) -> tuple[list[int], list[list[int]]]:
    """A whole multi-op PQL tree — K expression Counts plus M TopN
    exact-count blocks — as ONE compiled XLA computation over shared
    device-resident leaf slabs: one dispatch, one in-program reduction,
    one host fetch (``[2, K + Σ rows]`` hi/lo halves) for everything
    the tree needs. ``topn_items`` is ``[(expr, n_rows), ...]`` with
    ``rows_arrays[i]`` the matching [S, R_i, W] resident candidate
    block. Returns (count values, per-TopN count lists).

    The old lane paid one host↔device sync per *call*; a tree pays one.
    """
    _dispatch_gate()
    if leaf_arrays and leaf_arrays[0].shape[0] > slice_chunk_bound(
            mesh.shape[AXIS_SLICES]):
        raise ValueError("fused_tree_sharded: slice count above the"
                         " int32 hi/lo bound")
    from . import programs as programs_mod
    fn = programs_mod.fused_program(
        mesh, tuple(count_exprs),
        tuple((expr, int(rows.shape[1]))
              for (expr, _), rows in zip(topn_items, rows_arrays)),
        len(leaf_arrays))
    _note_dispatch(mesh, *leaf_arrays, *rows_arrays)
    flat = _run_hilo(fn, *leaf_arrays, *rows_arrays)
    counts = flat[:len(count_exprs)]
    out_topn: list[list[int]] = []
    off = len(count_exprs)
    for rows in rows_arrays:
        n = int(rows.shape[1])
        out_topn.append(flat[off:off + n])
        off += n
    return counts, out_topn


def _shard_topn_inter(expr, rows, leaves):
    """[S, R] per-(slice, row) intersection counts of candidate ``rows``
    [S, R, W] against ``expr`` over ``leaves`` [L, S, W] — the shared
    count body of the TopN programs."""
    words = rows
    if expr is not None:
        src = _eval_expr(expr, leaves)
        words = jnp.bitwise_and(rows, src[:, None, :])
    return jnp.sum(jax.lax.population_count(words).astype(jnp.int32),
                   axis=-1)


def hilo_combine(hilo) -> list[int]:
    """Decode one stacked [2, ...] (hi, lo) device output into exact
    Python ints: ``(hi << 16) + lo`` vectorized, one host fetch."""
    arr = np.asarray(hilo).astype(np.int64)
    return ((arr[0] << 16) + arr[1]).ravel().tolist()


def _filtered_counts(expr, rows, leaves, threshold, tanimoto):
    """[S, R] intersection counts with the reference's per-slice
    threshold/Tanimoto pruning applied BEFORE the slice reduction
    (fragment.go:560-614 — a slice's contribution drops when that
    slice's row count or intersection count fails the bar, then the
    executor sums the survivors; exact integer forms of the float
    comparisons, identical results). threshold/tanimoto are runtime
    scalars — one compiled program per (mesh, expr)."""
    inter = _shard_topn_inter(expr, rows, leaves)         # [S, R]
    rowc = _shard_topn_inter(None, rows, leaves[:0])
    srcc = _rows_popcount(expr, leaves)                   # [S]
    s = srcc[:, None]                                     # [S, 1]
    # cnt > srcc·t/100  ∧  cnt < srcc·100/t  ∧  inter > 0
    # ∧  ceil(100·inter / (cnt + srcc − inter)) > t
    keep_tan = ((100 * rowc > s * tanimoto)
                & (rowc * tanimoto < s * 100)
                & (inter > 0)
                & (100 * inter > tanimoto * (rowc + s - inter)))
    keep_thr = (rowc >= threshold) & (inter >= threshold)
    keep = jnp.where(tanimoto > 0, keep_tan, keep_thr)
    return jnp.where(keep, inter, 0)


@_fair_dispatch
def topn_filtered_sharded(mesh: Mesh, expr, rows: jax.Array,
                          leaf_arrays: list[jax.Array],
                          threshold: int = 1,
                          tanimoto: int = 0) -> list[int]:
    """TopN counts with per-slice threshold/Tanimoto pruning on device
    (see _filtered_counts). Same residency contract as
    topn_exact_sharded."""
    _dispatch_gate()
    if rows.shape[0] > slice_chunk_bound(mesh.shape[AXIS_SLICES]):
        raise ValueError("topn_filtered_sharded: slice count above the"
                         " int32 hi/lo bound")
    from . import programs as programs_mod
    fn = programs_mod.topn_program(mesh, expr, len(leaf_arrays),
                                   filtered=True)
    threshold = min(threshold, 2**31 - 1)  # counts never exceed 2^31
    _note_dispatch(mesh, rows, *leaf_arrays)
    return _run_hilo(fn, jnp.int32(threshold), jnp.int32(tanimoto),
                     rows, *leaf_arrays)[:rows.shape[1]]


@_fair_dispatch
def topn_exact_sharded(mesh: Mesh, expr, rows: jax.Array,
                       leaf_arrays: list[jax.Array]) -> list[int]:
    """TopN exact counts over a DEVICE-resident candidate block
    ``rows [n_slices, R, W]`` and per-leaf slabs (all sharded over the
    slice axis, e.g. from the residency cache). Single program — the
    caller bounds n_slices (slice_chunk_bound) and the block bytes.
    """
    _dispatch_gate()
    if rows.shape[0] > slice_chunk_bound(mesh.shape[AXIS_SLICES]):
        raise ValueError("topn_exact_sharded: slice count above the"
                         " int32 hi/lo bound — use topn_exact")
    from . import programs as programs_mod
    fn = programs_mod.topn_program(mesh, expr, len(leaf_arrays),
                                   filtered=False)
    _note_dispatch(mesh, rows, *leaf_arrays)
    return _run_hilo(fn, rows, *leaf_arrays)[:rows.shape[1]]


@_fair_dispatch
def topn_topk_sharded(mesh: Mesh, expr, rows: jax.Array,
                      leaf_arrays: list[jax.Array],
                      k: int) -> tuple[list[int], list[int]]:
    """Sourceless-TopN top-k over a DEVICE-resident candidate block:
    counts reduce AND the top-k selection happens inside one program
    (programs.topn_topk_program), so the host fetches [3, k] instead
    of the whole [2, R] count table. Returns (counts, row indices),
    count-descending with ascending-index tie-break — the host
    pairs_sort order."""
    _dispatch_gate()
    if rows.shape[0] > slice_chunk_bound(mesh.shape[AXIS_SLICES]):
        raise ValueError("topn_topk_sharded: slice count above the"
                         " int32 hi/lo bound")
    k = max(1, min(int(k), int(rows.shape[1])))
    from . import programs as programs_mod
    fn = programs_mod.topn_topk_program(mesh, expr, len(leaf_arrays), k)
    _note_dispatch(mesh, rows, *leaf_arrays)
    out = _run(fn, rows, *leaf_arrays).astype(np.int64)
    counts = ((out[0] << 16) + out[1]).tolist()
    return counts, out[2].tolist()


def shard_slices_axis1(mesh: Mesh, arr: np.ndarray) -> jax.Array:
    """Place ``[L, n_slices, ...]`` on the mesh, sharded over axis 1."""
    spec = [None] * arr.ndim
    spec[1] = AXIS_SLICES
    return jax.device_put(arr, NamedSharding(mesh, P(*spec)))


def _flatten_fold(expr):
    """(op, [leaf ids]) when ``expr`` is a pure left fold of one op over
    leaves — the shape _compile_device_expr builds for n-ary PQL calls.
    None for mixed trees. Iterative: a 1000-child Union is a 1000-deep
    left-leaning tuple tree, and recursing it would overflow Python's
    stack before XLA ever saw it."""
    op = expr[0]
    if op == "leaf":
        return None
    ids = []
    node = expr
    while isinstance(node, tuple) and node[0] == op:
        if node[2][0] != "leaf":
            return None
        ids.append(node[2][1])
        node = node[1]
    if node[0] != "leaf":
        return None
    ids.append(node[1])
    ids.reverse()
    return op, ids


def _eval_expr(expr, leaves):
    flat = _flatten_fold(expr)
    if (flat is not None and len(flat[1]) >= 3
            and flat[0] in ("or", "and", "andnot")):
        # Wide fold → one associative lax.reduce over the leaf axis
        # instead of a leaf-count-deep op chain. Left-fold Difference
        # rewrites exactly: ((a∖b)∖c)… = a ∧ ¬(b∨c∨…). (xor and any
        # other op fall through to the generic chain below.)
        op, ids = flat
        sel = leaves if list(ids) == list(range(leaves.shape[0])) \
            else leaves[jnp.asarray(ids)]
        if op == "or":
            return jax.lax.reduce(sel, np.uint32(0),
                                  jax.lax.bitwise_or, (0,))
        if op == "and":
            return jax.lax.reduce(sel, np.uint32(0xFFFFFFFF),
                                  jax.lax.bitwise_and, (0,))
        rest = jax.lax.reduce(sel[1:], np.uint32(0),
                              jax.lax.bitwise_or, (0,))
        return jnp.bitwise_and(sel[0], jnp.bitwise_not(rest))
    if expr[0] == "leaf":
        return leaves[expr[1]]
    return _BITWISE[expr[0]](_eval_expr(expr[1], leaves),
                             _eval_expr(expr[2], leaves))


@_fair_dispatch
def materialize_expr_sharded(mesh: Mesh, expr,
                             leaf_arrays: list[jax.Array]) -> np.ndarray:
    """[S, W] dense words of the expression bitmap: one sharded device
    fold over the leaf slabs (the materializing form of count_expr —
    BASELINE config 2's Union/Difference over many rows), fetched to
    host for roaring repack. No count reduction → no slice-count bound;
    wide folds reduce associatively on device (_eval_expr's lax.reduce
    path).
    """
    _dispatch_gate()
    from . import programs as programs_mod
    fn = programs_mod.materialize_program(mesh, expr, len(leaf_arrays))
    _note_dispatch(mesh, *leaf_arrays)
    return _run(fn, *leaf_arrays)


@_fair_dispatch
def bsi_range_sharded(mesh: Mesh, op: str, upred, depth: int,
                      plane_arrays: list[jax.Array]) -> np.ndarray:
    """[S, W] dense matched words of a BSI comparison: the whole
    bit-plane circuit (storage.bsi semantics, ops.kernels circuit
    body) over device-resident plane slabs — ``plane_arrays[0]`` the
    existence row, ``plane_arrays[1+i]`` offset-value bit i, each
    ``[n_slices, W]`` sharded over the slice axis — as ONE compiled
    SPMD program per (mesh, op, depth). The predicate travels as a
    traced LSB-first bit vector, so repeated range queries at one
    depth reuse the compilation. ``op`` "><" takes ``upred = (lo,
    hi)`` in offset space; everything else a single offset predicate.
    """
    _dispatch_gate()
    from ..ops import kernels
    if op == "><":
        lo, hi = upred
        pbits = kernels.bsi_predicate_bits(lo, depth)
        pbits2 = kernels.bsi_predicate_bits(hi, depth)
    else:
        pbits = kernels.bsi_predicate_bits(upred, depth)
        pbits2 = np.zeros(depth, dtype=np.uint32)
    from . import programs as programs_mod
    fn = programs_mod.bsi_range_program(mesh, op, len(plane_arrays))
    _note_dispatch(mesh, *plane_arrays)
    return _run(fn, pbits, pbits2, *plane_arrays)


# Device-block budget for one topn_exact call (mirrors the 256 MB
# per-block bound of the per-fragment path, fragment.py chunk=2048).
TOPN_BLOCK_BYTES = 256 << 20


@_fair_dispatch
def topn_exact(mesh: Mesh, expr, rows: np.ndarray,
               leaves: np.ndarray | None, threshold: int = 1,
               tanimoto: int = 0) -> list[int]:
    """[R] exact counts of each candidate row against ``expr`` (or the
    rows' own popcounts when expr is None), summed over all slices.
    threshold>1 / tanimoto engage the per-slice pruning program.

    Chunks both axes: slices at the int32 hi/lo bound and candidate
    rows by the device-block byte budget — counts are independent per
    row, additive per slice, and the pruning masks are per-slice, so
    any tiling is exact.
    """
    _dispatch_gate()
    from . import programs as programs_mod
    n_dev = mesh.shape[AXIS_SLICES]
    filtered = threshold > 1 or tanimoto > 0
    if filtered:
        # Counts never exceed 2^31, so clamping is semantically exact
        # (and jnp.int32 would raise on larger Python ints).
        threshold = min(threshold, 2**31 - 1)
        fn = functools.partial(
            programs_mod.topn_block_program(mesh, expr, filtered=True),
            jnp.int32(threshold), jnp.int32(tanimoto))
    else:
        fn = programs_mod.topn_block_program(mesh, expr, filtered=False)
    n_slices, n_rows, n_words = rows.shape
    slice_chunk = min(slice_chunk_bound(n_dev), n_slices) or 1
    row_chunk = max(1, TOPN_BLOCK_BYTES // (slice_chunk * n_words * 4))
    totals = [0] * n_rows
    for s_off in range(0, n_slices, slice_chunk):
        lc = None
        if leaves is not None:
            lc = leaves[:, s_off:s_off + slice_chunk]
        for r_off in range(0, n_rows, row_chunk):
            rc = rows[s_off:s_off + slice_chunk, r_off:r_off + row_chunk]
            lcc = lc if lc is not None else \
                np.zeros((0, rc.shape[0], 1), dtype=np.uint32)
            # Bucket-stable slice padding: zero slices are the count
            # identity, and the bucketed shape reuses one compiled
            # program across nearby slice counts.
            rc = programs_mod.bucket_pad(rc, 0, n_dev)
            lcc = programs_mod.bucket_pad(lcc, 1, n_dev)
            _note_dispatch(mesh, rc, lcc)  # per chunk: one program each
            with sched_context.stage("upload"):
                rc_dev = shard_slices(mesh, rc)
                lcc_dev = shard_slices_axis1(mesh, lcc)
            counts = _run_hilo(fn, rc_dev, lcc_dev)
            for r in range(rc.shape[1]):
                totals[r_off + r] += counts[r]
    return totals


@functools.lru_cache(maxsize=None)
def _topn_fn(mesh: Mesh, op: str, k: int):
    """rows [S, R, W] × src [S, W] → (top-k counts, top-k row indices).

    Per-slice intersection counts for ALL candidate rows in one fused
    pass (the vectorized replacement for the reference's sequential
    threshold loop, fragment.go:560-614), psum'd over the slice axis,
    gathered over the row axis, then a single device top_k.
    """
    bitwise = _BITWISE[op]

    def per_shard(rows, src):  # rows: [S/n, R/m, W], src: [S/n, W]
        words = bitwise(rows, src[:, None, :])
        pc = jax.lax.population_count(words).astype(jnp.int32)
        counts = jnp.sum(pc, axis=(0, 2))              # [R/m]
        counts = jax.lax.psum(counts, AXIS_SLICES)     # slice reduce (ICI)
        counts = jax.lax.all_gather(counts, AXIS_ROWS,
                                    tiled=True)        # [R]
        vals, idx = jax.lax.top_k(counts, k)
        return vals, idx

    # check_vma off: the all_gather over ``rows`` makes counts replicated,
    # but the varying-axis inference can't prove it.
    return _finalize_program(jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(AXIS_SLICES, AXIS_ROWS), P(AXIS_SLICES)),
        out_specs=(P(), P()), check_vma=False), f"topn_{op}_top{k}")


def topn_counts(mesh: Mesh, op: str, rows: jax.Array, src: jax.Array,
                k: int) -> tuple[np.ndarray, np.ndarray]:
    """(counts, row_indices) of the k candidate rows with the largest
    ``count(op(row, src))`` across all slices."""
    vals, idx = _topn_fn(mesh, op, k)(rows, src)
    return np.asarray(vals), np.asarray(idx)


@functools.lru_cache(maxsize=None)
def _query_step_fn(mesh: Mesh, k: int):
    """The flagship distributed query step, jitted over the full mesh.

    One fused SPMD program: Count(Intersect) + Count(Union) over a
    slice-sharded pair of bitmap slabs, plus TopN(k) of a row-sharded
    candidate block against the intersection — i.e. configs 4 and 5 of
    BASELINE.md in a single compiled step. Collectives: psum over
    ``slices``, all_gather over ``rows``.
    """

    def per_shard(a, b, rows):
        # a, b: [S/n, W]; rows: [S/n, R/m, W]
        inter = jnp.bitwise_and(a, b)
        union = jnp.bitwise_or(a, b)
        pc_i = jnp.sum(jax.lax.population_count(inter).astype(jnp.int32))
        pc_u = jnp.sum(jax.lax.population_count(union).astype(jnp.int32))
        n_inter = jax.lax.psum(pc_i, AXIS_SLICES)
        n_union = jax.lax.psum(pc_u, AXIS_SLICES)
        words = jnp.bitwise_and(rows, inter[:, None, :])
        counts = jnp.sum(jax.lax.population_count(words).astype(jnp.int32),
                         axis=(0, 2))
        counts = jax.lax.psum(counts, AXIS_SLICES)
        counts = jax.lax.all_gather(counts, AXIS_ROWS, tiled=True)
        top_vals, top_ids = jax.lax.top_k(counts, k)
        return n_inter, n_union, top_vals, top_ids

    return _finalize_program(jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(AXIS_SLICES), P(AXIS_SLICES),
                  P(AXIS_SLICES, AXIS_ROWS)),
        out_specs=(P(), P(), P(), P()), check_vma=False), f"query_step_top{k}")


def query_step(mesh: Mesh, a: jax.Array, b: jax.Array, rows: jax.Array,
               k: int):
    """Run the fused distributed query step; see _query_step_fn."""
    n_i, n_u, vals, ids = _query_step_fn(mesh, k)(a, b, rows)
    return int(n_i), int(n_u), np.asarray(vals), np.asarray(ids)

# Every lru_cache'd shard_map program builder still hosted here, for
# compile_stats()'s hit/miss aggregation (the global-view catalogue's
# caches live in parallel.programs.PROGRAM_CACHES and are folded in by
# _all_program_caches()).
_PROGRAM_CACHES = (_densify_sharded_fn, _count_fn, _topn_fn,
                   _query_step_fn)
