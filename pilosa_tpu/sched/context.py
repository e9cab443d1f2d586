"""QueryContext: per-query identity, deadline budget, and cancellation.

One QueryContext rides a query from the HTTP front door through the
executor's map-reduce fan-out, the device dispatch layer
(parallel.mesh), and the cluster client's remote legs. It carries

- an **id** (propagated to peers as ``X-Pilosa-Query-Id``, so every
  node's /debug/queries lists the same query and a cluster-wide cancel
  can find its legs),
- a **deadline** parsed from ``X-Pilosa-Deadline`` (remaining seconds —
  the fan-out form: peers inherit the *remaining* budget, not the
  original) or ``?timeout=`` (Go-style duration on the entry request),
- a **cancel flag** set by DELETE /debug/queries/{id} (locally or via
  the cluster broadcast), and
- the **stage clock**: self-time stages that tile the request from
  socket to socket (``StageClock`` below) — self seconds and entries
  per stage, CPU seconds per thread, for /debug/queries, the slow log,
  kept traces, the ``queryStages`` totals at /debug/vars and, under a
  profiler session, ``pilosa.<stage>`` segments on the profiler's own
  clock.

Checks are cooperative: every layer that can block or loop calls
``ctx.check()`` (or module-level ``check_current()`` from code that
does not take a ctx argument, e.g. the mesh dispatch functions) and
gets a QueryDeadlineError / QueryCancelledError the moment the budget
is gone. The context travels between executor worker threads via
``use()``'s thread-local, set by the executor around each leg.
"""

from __future__ import annotations

import gc
import os
import random
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Optional

from ..errors import (QueryCancelledError, QueryDeadlineError,
                      QueryKilledError)

# Lanes the admission controller schedules between. LANES is the
# canonical display order (the `pilosa-tpu top` per-lane table and
# any other lane-enumerating consumer read it from here instead of
# hardcoding the strings).
LANE_READ = "read"
LANE_WRITE = "write"
LANE_ADMIN = "admin"
LANES = (LANE_READ, LANE_WRITE, LANE_ADMIN)

# Wire headers for cluster fan-out propagation. The tenant header
# carries the scheduling/accounting principal (= index, today) onto
# remote legs — same pattern as the deadline: a peer inherits the
# coordinator's principal, so per-tenant cost ceilings and chargeback
# roll-ups hold cluster-wide even though forwarded legs bypass
# admission.
DEADLINE_HEADER = "X-Pilosa-Deadline"
QUERY_ID_HEADER = "X-Pilosa-Query-Id"
TENANT_HEADER = "X-Pilosa-Tenant"


# -- the stage clock ---------------------------------------------------------
# The stage names are a contract: counter keys in /debug/vars
# ``queryStages``, span names in kept traces, ``pilosa.<name>`` in the
# profiler. On the connection thread, in order: http_read, parse, setup,
# admission, execute (self), plan, route, pack, upload, fill_wait,
# dispatch (+ compile), fetch, merge, legs_wait, commit, finish, encode,
# http_write; ``leg`` is the base stage of a map-reduce worker thread.
# docs/OBSERVABILITY.md "Trace contract" has the table.

# WSGI environ key under which the HTTP front end hands its clock
# (opened at ``recv``, closed after ``sendall``) to the handler.
CLOCK_ENVIRON = "pilosa.stage_clock"

# Ended stages a clock keeps for the kept trace (obs.trace caps a
# trace's own spans the same way).
MAX_STAGE_SPANS = 512

_annotation = None      # jax.profiler.TraceAnnotation, resolved lazily


def _session_live() -> bool:
    """Is a profiler session recording? One flag test (no switch of
    our own: the session IS the switch). A process that has not
    imported jax has none — look again next time instead of paying the
    import here; nor has one that is half way through importing it
    (the collector's hook asks from wherever a collection starts)."""
    global _annotation
    if _annotation is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        if profiler is None:
            return False
        _annotation = profiler.TraceAnnotation
    return _annotation.is_enabled()


def _annotate(name: str):
    """An entered ``TraceAnnotation("pilosa.<name>")``: one flat segment
    on this thread's /host:CPU line, on the clock the device trace
    uses; None with no session."""
    if not _session_live():
        return None
    ann = _annotation("pilosa." + name)
    ann.__enter__()
    return ann


# -- who held the interpreter -------------------------------------------------
# One interpreter serves every thread, so a request's wait is mostly a
# wait for whoever runs. Three readings of that, all behind /debug/vars
# ``interpreter`` and ``backgroundTicks`` (docs/OBSERVABILITY.md):
# every collector pass is bracketed (``_gc_hook``), every background
# tick is a named *hold* while it is open and says how late it woke
# (``background_tick``), and a stretch in which NO request thread
# crossed a stage boundary although one was inside a stage throughout
# is a *quiet interval*, named after the hold that covers it.

# No boundary for this long, seen from inside a stage, is a quiet
# interval: the hot cells' ordinary gaps are 5-14 ms, the stops that
# PERF.md section 7 rows 22 and 25 describe 86 ms to 3.3 s.
QUIET_S = 0.025
HOLD_MIN_S = 0.001      # a hold shorter than this explains no interval

# perf_counter reading of the last stage boundary crossed by ANY request
# thread. Loaded and stored without a lock at every boundary, with no
# call between the two (the interpreter switches threads only at one):
# a lost store is an error of microseconds against QUIET_S, and a
# second thread that does see the same stop is dropped in ``_quiet``.
_last_any = 0.0
_quiet_end = 0.0        # where the last recorded quiet interval ended
# Closed holds (t0, t1, name, CPU seconds of the holding thread) and
# open ticks {thread id: (t0, name, thread CPU at t0, its CPU clock)}.
_HOLDS: deque = deque(maxlen=64)
_OPEN_HOLDS: dict[int, tuple] = {}
_gc_open: Optional[tuple] = None    # (t0, generation, annotation)
# [n, wall seconds, max seconds] a generation, and objects collected.
# Only the collecting thread writes them (collections do not nest), so
# the hook takes no lock: it may run inside any ``with _TOTALS_MU``.
_GC = ([0, 0.0, 0.0], [0, 0.0, 0.0], [0, 0.0, 0.0])
_GC_NAMES = ("gc.gen0", "gc.gen1", "gc.gen2")
_gc_collected = 0
_QUIET = {"n": 0, "wall": 0.0, "byHolder": {}, "byStage": {}}
_QUIET_RECENT: deque = deque(maxlen=32)


def _gc_hook(phase: str, info: dict) -> None:
    """``gc.callbacks`` entry: a collection holds the interpreter from
    ``start`` to ``stop``. Counted always; under a profiler session
    also a ``pilosa.gc.gen<g>`` segment on the collecting thread."""
    global _gc_open, _gc_collected
    if phase == "start":
        gen = info["generation"]
        _gc_open = (time.perf_counter(), gen, _annotate(_GC_NAMES[gen]))
        return
    t1 = time.perf_counter()
    opened, _gc_open = _gc_open, None
    if opened is None:      # registered between a start and its stop
        return
    t0, gen, ann = opened
    if ann is not None:
        ann.__exit__(None, None, None)
    dt = t1 - t0
    g = _GC[gen]
    g[0] += 1
    g[1] += dt
    if dt > g[2]:
        g[2] = dt
    _gc_collected += info["collected"]
    if dt >= HOLD_MIN_S:
        _HOLDS.append((t0, t1, _GC_NAMES[gen], dt))


def _holder(t0: float, t1: float) -> tuple[str, float]:
    """(name, seconds) of the hold that best explains ``[t0, t1]``:
    closed ones from the ring, open ticks and a running collection
    counted up to ``t1`` (a tick whose long call has just returned has
    not reached its ``finally`` when the first waiter runs). A hold
    whose thread was on a CPU for under half of what it overlaps does
    not count: a tick asleep on a disk or a compile, or itself waiting
    for the interpreter, holds nothing (half, not all of it: a holder
    the machine parked for a while still holds). The one that overlaps
    most, to the millisecond; of equals the shorter (a tick inside a
    tick). It has to cover half of the interval, else ``unknown``."""
    holds = list(_HOLDS)
    for h0, name, cpu0, clock_id in list(_OPEN_HOLDS.values()):
        try:
            cpu = time.clock_gettime(clock_id) - cpu0
        except OSError:         # the thread is gone, or no such clock
            cpu = t1 - h0
        holds.append((h0, t1, name, cpu))
    running = _gc_open
    if running is not None:
        holds.append((running[0], t1, _GC_NAMES[running[1]],
                      t1 - running[0]))
    best, best_key, best_s = "unknown", (0.0, 0.0), 0.0
    for h0, h1, name, cpu in holds:
        s = min(h1, t1) - max(h0, t0)
        key = (round(s, 3), h0 - h1)
        if s > 0.0 and cpu >= s / 2 and key > best_key:
            best, best_key, best_s = name, key, s
    if best_s < (t1 - t0) / 2:
        return "unknown", 0.0
    return best, best_s


def _quiet(stage: str, wall: float, gap: float) -> None:
    """Record the quiet interval ``[wall - gap, wall]`` that the
    calling thread saw from inside ``stage``. Off the hot path: a
    boundary calls this only where ``gap >= QUIET_S``."""
    global _quiet_end
    holder, held = _holder(wall - gap, wall)
    rec = {"at": time.time() - gap, "ms": round(gap * 1e3, 3),
           "stage": stage, "holder": holder,
           "holderMs": round(held * 1e3, 3)}
    with _TOTALS_MU:
        if wall - gap < _quiet_end:     # the stop another thread recorded
            return
        _quiet_end = wall
        _QUIET["n"] += 1
        _QUIET["wall"] += gap
        _add(_QUIET["byHolder"], {holder: (1, gap)})
        _add(_QUIET["byStage"], {stage: (1, gap)})
        _QUIET_RECENT.append(rec)


class StageClock:
    """Self-time stages of ONE thread: a stack of open stages and, per
    stage name, ``[entries, wall seconds]``.

    Entering a stage charges the time since the last boundary to the
    stage that was on top and suspends it; leaving charges it to the
    stage itself and resumes the parent — so every stage holds SELF
    time and the stages sum to the thread's time between the first and
    the last boundary by construction. ``switch`` replaces the top
    stage: the form for the connection thread's top-level sequence
    (http_read → parse → … → http_write), which never leaves the stack
    empty in between. A boundary reads ``perf_counter`` once, and
    compares it with the last boundary of ANY thread (``_last_any``):
    a boundary that ends a wait of ``QUIET_S`` in which no other request
    thread crossed one records a quiet interval (``_quiet``). A boundary
    that fills an empty stack only sets the mark: the thread was in no
    stage, so a request reaching an idle server is no stop.

    The thread's CPU clock is read only where the stack fills and where
    it empties (twice a request on the connection thread, twice a leg):
    ``cpu`` is the CPU seconds of the whole tiling, and wall − CPU is
    time the thread was not running (the GIL, a lock, the device, the
    socket). Not at every boundary, and not per stage: on the sandboxed
    kernel the chip's host runs (gVisor), ``thread_time`` costs 6 µs a
    call and ticks every 10 ms (my chip run, PR 25) — right summed over
    thousands of requests, noise within one, and 40 reads a request
    would cost 5 % of a 5 ms Count. Only the owning thread calls the
    mutators."""

    __slots__ = ("acct", "cpu", "ctx", "requests", "spans", "tid",
                 "_stack", "_wall", "_cpu0", "_ann")

    def __init__(self, name: str = "", start: Optional[float] = None):
        global _last_any
        self.acct: dict[str, list] = {}
        self.cpu = 0.0
        self.ctx: Optional["QueryContext"] = None
        self.requests = 1       # the pipelined batch lane sets its size
        self._stack: list[tuple] = []     # (name, wall at entry, tags)
        self._wall = start or time.perf_counter()
        self._cpu0 = 0.0
        self._ann = None
        # Ended stages, whole (entry to exit, children inside): what a
        # kept trace shows. Plain tuples (name, entry, exit, tags) on
        # this clock; ``QueryContext.stage_spans`` makes Spans of them
        # only when a trace is read.
        self.spans: list[tuple] = []
        self.tid = threading.get_ident()
        if name:
            _last_any = self._wall
            self._cpu0 = time.thread_time()
            self._begin(name, self._wall, None)

    def _mark(self, name: str) -> None:
        """Close this thread's profiler segment and open ``name``'s:
        one name at a time per thread, never nested."""
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self._ann = _annotate(name) if name else None

    def _leave(self) -> None:
        name, t0, tags = self._stack.pop()
        ctx = self.ctx
        if ((ctx is None or ctx.trace is not None)
                and len(self.spans) < MAX_STAGE_SPANS):
            self.spans.append((name, t0, self._wall, tags))

    def _begin(self, name: str, wall: float,
               tags: Optional[dict]) -> None:
        a = self.acct.get(name)
        if a is None:
            self.acct[name] = [1, 0.0]
        else:
            a[0] += 1
        self._stack.append((name, wall, tags))
        if self._ann is not None or _session_live():
            self._mark(name)

    def push(self, name: str, tags: Optional[dict] = None) -> None:
        """The boundary into ``name``: charge the time since the last
        one to the stage on top, suspend it, enter ``name``."""
        global _last_any
        wall = time.perf_counter()
        gap = wall - _last_any
        _last_any = wall
        if self._stack:
            self.acct[self._stack[-1][0]][1] += wall - self._wall
            if gap >= QUIET_S:
                _quiet(self._stack[-1][0], wall, gap)
        else:
            self._cpu0 = time.thread_time()
        self._wall = wall
        self._begin(name, wall, tags)

    def pop(self) -> None:
        """The boundary out of the stage on top: charge it, resume its
        parent."""
        global _last_any
        wall = time.perf_counter()
        gap = wall - _last_any
        _last_any = wall
        stack = self._stack
        self.acct[stack[-1][0]][1] += wall - self._wall
        if gap >= QUIET_S:
            _quiet(stack[-1][0], wall, gap)
        self._wall = wall
        self._leave()
        if not stack:
            self.cpu += time.thread_time() - self._cpu0
        if self._ann is not None or _session_live():
            self._mark(stack[-1][0] if stack else "")

    def switch(self, name: str) -> None:
        """End the stage on top and begin ``name`` in its place, in one
        boundary (a push where nothing is open)."""
        global _last_any
        if not self._stack:
            return self.push(name)
        wall = time.perf_counter()
        gap = wall - _last_any
        _last_any = wall
        self.acct[self._stack[-1][0]][1] += wall - self._wall
        if gap >= QUIET_S:
            _quiet(self._stack[-1][0], wall, gap)
        self._wall = wall
        self._leave()
        self._begin(name, wall, None)

    def close(self) -> None:
        """The last boundary: every open stage ends, the profiler
        segment closes, and a clock that served a query folds into the
        process totals. The HTTP front end calls this once the
        response has been handed to the socket."""
        global _last_any
        if self._stack:
            wall = time.perf_counter()
            gap = wall - _last_any
            _last_any = wall
            self.acct[self._stack[-1][0]][1] += wall - self._wall
            if gap >= QUIET_S:
                _quiet(self._stack[-1][0], wall, gap)
            self._wall = wall
            self.cpu += time.thread_time() - self._cpu0
            while self._stack:
                self._leave()
        self._mark("")
        ctx, self.ctx = self.ctx, None
        if ctx is not None:
            trace = ctx.trace
            if trace is not None and trace.keep_reason:
                trace.seal()    # a kept trace outlives its query
            _fold(ctx, self)


class _StageCM:
    """``with`` form of push/pop; pops on an exception too."""

    __slots__ = ("_clock", "_name", "_tags")

    def __init__(self, clock: StageClock, name: str,
                 tags: Optional[dict]):
        self._clock = clock
        self._name = name
        self._tags = tags

    def __enter__(self):
        self._clock.push(self._name, self._tags)
        return self

    def tag(self, **tags) -> None:
        """Tags learned while the stage is open, for a stage entered
        with some (the clock holds that dict; one entered with none has
        no span tags to add to)."""
        if self._tags is not None:
            self._tags.update(tags)

    def __exit__(self, exc_type, exc, tb):
        self._clock.pop()
        return False


class _SpanCM:
    """A tagged wall-clock span of the kept trace that is NOT a stage
    of the tiling (fan-out events: rpc, failover, hedge, ...)."""

    __slots__ = ("_ctx", "_name", "_tags", "_t0")

    def __init__(self, ctx: "QueryContext", name: str,
                 tags: Optional[dict]):
        self._ctx = ctx
        self._name = name
        self._tags = tags

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def tag(self, **tags) -> None:
        """Tags learned while the span is open."""
        self._tags = {**(self._tags or {}), **tags}

    def __exit__(self, exc_type, exc, tb):
        ctx = self._ctx
        ctx.trace.add_span(self._name, ctx.wall_at(self._t0),
                           time.perf_counter() - self._t0, self._tags)
        return False


class _Nop:
    __slots__ = ()

    def __enter__(self):
        return self

    def tag(self, **tags) -> None:
        pass

    def __exit__(self, exc_type, exc, tb):
        return False


NOP = _Nop()


# Process totals behind /debug/vars: ``queryStages`` by lane (non-remote
# /query requests, folded once each when the response is on the socket),
# ``backgroundTicks`` by loop and ``interpreter``'s quiet intervals. One
# lock, taken once a request / tick / quiet interval.
_TOTALS_MU = threading.Lock()
_QUERY_TOTALS: dict[str, dict] = {}
_BG_TOTALS: dict[str, list] = {}    # loop -> [n, wall, cpu, lateN, late]


def _add(into: dict, acct: dict) -> None:
    for name, (n, wall) in acct.items():
        t = into.get(name)
        if t is None:
            into[name] = [n, wall]
        else:
            t[0] += n
            t[1] += wall


def _fold(ctx: "QueryContext", clock: StageClock) -> None:
    if ctx.remote or ctx.lane not in (LANE_READ, LANE_WRITE):
        return
    off = ctx.stage_totals()[1]
    off_cpu = ctx.stage_cpu()[1]
    with _TOTALS_MU:
        lane = _QUERY_TOTALS.get(ctx.lane)
        if lane is None:
            lane = _QUERY_TOTALS[ctx.lane] = {
                "requests": 0, "cpu": 0.0, "offCpu": 0.0,
                "stages": {}, "offThread": {}}
        lane["requests"] += clock.requests
        lane["cpu"] += clock.cpu
        lane["offCpu"] += off_cpu
        _add(lane["stages"], clock.acct)
        _add(lane["offThread"], off)


def _acct_json(acct: dict) -> dict:
    return {name: {"n": n, "wallUs": round(wall * 1e6)}
            for name, (n, wall) in acct.items()}


def stage_totals() -> dict:
    """The /debug/vars blocks ``queryStages``, ``backgroundTicks`` and
    ``interpreter``."""
    with _TOTALS_MU:
        gc_json: dict = {
            "gen%d" % gen: {"n": n, "wallUs": round(wall * 1e6),
                            "maxUs": round(longest * 1e6)}
            for gen, (n, wall, longest) in enumerate(_GC)}
        gc_json["collected"] = _gc_collected
        return {
            "queryStages": {
                lane: {"requests": t["requests"],
                       "cpuUs": round(t["cpu"] * 1e6),
                       "offThreadCpuUs": round(t["offCpu"] * 1e6),
                       "stages": _acct_json(t["stages"]),
                       "offThread": _acct_json(t["offThread"])}
                for lane, t in _QUERY_TOTALS.items()},
            "backgroundTicks": {
                loop: {"n": n, "wallUs": round(wall * 1e6),
                       "cpuUs": round(cpu * 1e6),
                       "lateN": late_n, "lateUs": round(late * 1e6)}
                for loop, (n, wall, cpu, late_n, late)
                in _BG_TOTALS.items()},
            "interpreter": {
                "gc": gc_json,
                "quiet": {"n": _QUIET["n"],
                          "wallUs": round(_QUIET["wall"] * 1e6),
                          "thresholdMs": round(QUIET_S * 1e3),
                          "byHolder": _acct_json(_QUIET["byHolder"]),
                          "byStage": _acct_json(_QUIET["byStage"])},
                "recent": list(_QUIET_RECENT)}}


def timed_wait(stop: threading.Event, interval: float) -> Optional[float]:
    """``stop.wait(interval)`` for a loop that ticks on a fixed
    interval: the ``perf_counter`` time at which the wait was due to
    end, for ``background_tick(loop, due=...)``; None once ``stop`` is
    set."""
    due = time.perf_counter() + interval
    return None if stop.wait(interval) else due


@contextmanager
def background_tick(loop: str, due: Optional[float] = None):
    """Around ONE tick of a background loop: the same wall/CPU/entries
    counter under ``backgroundTicks[loop]`` and a ``pilosa.bg.<loop>``
    segment on the profiler's clock, so what the loops cost a served
    request (the GIL they hold) can be read beside its stages. While
    open the tick is a hold ``bg.<loop>`` that a quiet interval can be
    named after. ``due`` is when the loop's timed wait should have
    ended (``timed_wait``): how much later the tick begins is what a
    thread pays to get the interpreter back after any release, counted
    under ``lateUs`` / ``lateN``."""
    ann = _annotate("bg." + loop)
    wall, cpu = time.perf_counter(), time.thread_time()
    tid = threading.get_ident()
    outer = _OPEN_HOLDS.get(tid)    # ``history`` ticks inside ``runtime``
    _OPEN_HOLDS[tid] = (wall, "bg." + loop, cpu,
                        time.pthread_getcpuclockid(tid))
    try:
        yield
    finally:
        t1 = time.perf_counter()
        dw = t1 - wall
        dc = time.thread_time() - cpu
        if dw >= HOLD_MIN_S:
            _HOLDS.append((wall, t1, "bg." + loop, dc))
        if outer is None:
            _OPEN_HOLDS.pop(tid, None)
        else:
            _OPEN_HOLDS[tid] = outer
        if ann is not None:
            ann.__exit__(None, None, None)
        late = 0.0 if due is None else max(0.0, wall - due)
        with _TOTALS_MU:
            t = _BG_TOTALS.get(loop)
            if t is None:
                t = _BG_TOTALS[loop] = [0, 0.0, 0.0, 0, 0.0]
            t[0] += 1
            t[1] += dw
            t[2] += dc
            if due is not None:
                t[3] += 1
                t[4] += late


gc.callbacks.append(_gc_hook)


# -- query ids ---------------------------------------------------------------
# A coordinator read's id is 64 bits from a generator seeded ONCE a
# process from the kernel's pool: as unlikely to collide across the
# nodes of a cluster as the truncated uuid4 it replaces, and drawn
# without a system call. ``uuid.uuid4()`` is ``os.urandom(16)``, a
# ``getrandom`` that CPython makes with the interpreter lock released:
# alone 20 us, under eight serving threads the only point of a read's
# ``setup`` stage at which the connection thread let go of the
# interpreter and queued to get it back (1.58 of the stage's 1.65 ms;
# PERF.md, PR 30). ``getrandbits`` is one C call under the lock, so
# concurrent draws need no lock of their own. A forked child reseeds:
# it must not replay its parent's sequence.
def _seed_ids() -> None:
    global _id_bits
    _id_bits = random.Random(os.urandom(16)).getrandbits


_seed_ids()
os.register_at_fork(after_in_child=_seed_ids)


def new_query_id() -> str:
    """16 hex characters, 64 fresh bits."""
    return "%016x" % _id_bits(64)


class QueryContext:
    """Lifecycle state of one in-flight query."""

    def __init__(self, pql: str = "", index: str = "",
                 lane: str = LANE_READ,
                 timeout_s: Optional[float] = None,
                 id: Optional[str] = None, remote: bool = False,
                 node: str = "", tenant: str = "",
                 clock: Optional[StageClock] = None):
        self.id = id or new_query_id()
        self.pql = pql
        self.index = index
        self.lane = lane
        # Scheduling/accounting principal (sched.tenants): the index
        # by default, the X-Pilosa-Tenant header on forwarded legs.
        # Empty = the default tenant (bare contexts in tests).
        self.tenant = tenant or index
        self.remote = remote
        self.node = node
        self.started = time.monotonic()
        self.started_wall = time.time()
        self._started_perf = time.perf_counter()
        self.deadline = (self.started + timeout_s
                         if timeout_s else None)
        self.state = "queued"
        self.cancel_reason = ""
        self._cancelled = threading.Event()
        self._mu = threading.Lock()
        # The stage clocks, one a thread that ran a stage of this
        # query. The request thread's (the constructing thread; the
        # HTTP front end's clock when it handed one over) tiles the
        # request; the others (map-reduce workers bound with ``use``)
        # are kept apart so that sum stays a tiling.
        self._owner = threading.get_ident()
        self._clocks: dict[int, StageClock] = {}
        if clock is not None:
            clock.ctx = self
            self._clocks[self._owner] = clock
        self.legs: list[dict] = []
        # Distributed-tracing attachment (obs.trace.Trace), bound by
        # the tracer when tracing is on. None (the default) is the
        # no-allocation fast path: a stage records no span and
        # span() returns the shared no-op.
        self.trace = None
        # Resource-accounting attachment (obs.accounting.QueryCost),
        # bound by the serving layer when accounting is on. Same
        # contract as trace: None means every note_* site records
        # nothing.
        self.cost = None
        # Per-tenant cost policy (sched.tenants.TenantRegistry.install):
        # a callable check() consults at every cooperative checkpoint —
        # the stage boundaries — and which raises QueryKilledError the
        # moment the ledger crosses a ceiling. None (the default) costs
        # one attribute read per check.
        self.cost_policy = None
        # Set by the cost policy when it kills this query: check()
        # then raises QueryKilledError (not the plain cancel) from
        # EVERY thread touching this context, so the HTTP layer maps
        # the distinct status deterministically whichever leg
        # surfaces first.
        self.killed_by = ""
        # Fault-event flags the tail sampler's keep decision reads at
        # query end ("breaker", "failover", "failpoint", "partial"):
        # set by the choke points that observe the event (client
        # circuit-open, executor failover, failpoints.hit). Set.add is
        # GIL-atomic; no lock needed.
        self.flags: set[str] = set()
        # Filled at query end by the serving layer: whether this
        # query's trace was kept and why — the slow log cross-links on
        # these so /debug/queries/slow points at the persisted trace.
        self.trace_kept = False
        self.keep_reason = ""
        # Query-plan attachment (plan.record.PlanRecord), bound by the
        # executor when the planner handles this query. Same contract
        # as trace/cost: None means the planner sat this one out.
        # ``profile`` is the ?profile=1 flag — it asks the executor to
        # pay for exact per-node actual cardinalities (ANALYZE).
        self.plan = None
        self.profile = False
        # Workload-capture cross-links (obs.capture), filled by the
        # serving layer at query end: the canonical result digest
        # (the X-Pilosa-Result-Digest value) and the capture-record id
        # — a slow-log line names the exact replayable record.
        self.result_digest = ""
        self.capture_id = 0

    def note_flag(self, name: str) -> None:
        """Record a fault-event flag for the tail sampler (no-op
        semantics: flags only widen the keep decision)."""
        self.flags.add(name)

    # -- budget --------------------------------------------------------------

    def remaining(self) -> Optional[float]:
        """Seconds of budget left; None means no deadline. Can go
        negative once expired (callers clamp as needed)."""
        if self.deadline is None:
            return None
        return self.deadline - time.monotonic()

    def expired(self) -> bool:
        return (self.deadline is not None
                and time.monotonic() >= self.deadline)

    def elapsed(self) -> float:
        return time.monotonic() - self.started

    # -- cancellation --------------------------------------------------------

    def cancelled(self) -> bool:
        return self._cancelled.is_set()

    def cancel(self, reason: str = "cancelled") -> None:
        with self._mu:
            if not self._cancelled.is_set():
                self.cancel_reason = reason
                self.state = "cancelled"
            self._cancelled.set()

    def check(self) -> None:
        """Raise if this query must stop. The single cooperative
        cancellation point every lifecycle-aware layer calls — which
        makes it the per-tenant cost policy's stage-boundary hook
        too (the policy kills by cancelling, so a killed query stops
        at exactly the same points a cancelled one does)."""
        if self._cancelled.is_set():
            if self.killed_by:
                raise QueryKilledError(
                    f"query {self.id} killed by {self.killed_by}"
                    + (f": {self.cancel_reason}" if self.cancel_reason
                       else ""))
            raise QueryCancelledError(
                f"query {self.id} cancelled"
                + (f": {self.cancel_reason}" if self.cancel_reason
                   else ""))
        if self.expired():
            self.state = "expired"
            raise QueryDeadlineError(
                f"query {self.id}: deadline exceeded after"
                f" {self.elapsed():.3f}s")
        if self.cost_policy is not None:
            self.cost_policy(self)

    # -- bookkeeping ---------------------------------------------------------

    def clock(self) -> StageClock:
        """The calling thread's stage clock for this query."""
        tid = threading.get_ident()
        clock = self._clocks.get(tid)
        if clock is None:
            # No back-reference: a context and its clocks must not form
            # a cycle (every request would leave garbage only the
            # cyclic collector can free). The request thread's clock
            # holds its context until ``close`` folds it, and no longer.
            clock = self._clocks[tid] = StageClock()
        return clock

    def stage(self, name: str, **tags) -> _StageCM:
        """One stage of the calling thread's tiling, as a ``with``
        block (accumulating: a stage may run more than once; nested
        stages suspend their parent). When a trace is attached the
        stage doubles as a span carrying ``tags``."""
        clock = self.clock()
        if not tags and clock._stack and clock._stack[-1][0] == name:
            return NOP      # already in it (a recursive or inner route)
        return _StageCM(clock, name, tags or None)

    def span(self, name: str, **tags):
        """A tagged wall-clock span on the kept trace that is not a
        stage of the tiling (fan-out events); the shared no-op when no
        trace is attached."""
        if self.trace is None:
            return NOP
        return _SpanCM(self, name, tags or None)

    def stage_spans(self) -> list[tuple]:
        """Every ended stage of every thread as (name, wall start,
        seconds, tags, thread id): the spans a trace of this query
        shows, on the wall clock its peers share."""
        out = []
        for clock in list(self._clocks.values()):
            for name, t0, t1, tags in list(clock.spans):
                out.append((name, self.wall_at(t0), t1 - t0, tags,
                            clock.tid))
        return out

    def wall_at(self, t_perf: float) -> float:
        """Wall-clock seconds of a ``perf_counter`` reading: the
        context's wall start plus the monotonic offset (no second
        wall-clock read a span)."""
        return self.started_wall + (t_perf - self._started_perf)

    def _own_and_other_clocks(self) -> tuple:
        clocks = dict(self._clocks)
        return clocks.pop(self._owner, None), list(clocks.values())

    def stage_totals(self) -> tuple[dict, dict]:
        """({stage: [n, wall s]} of the request thread, the same
        summed over every other thread that ran a stage)."""
        own, others = self._own_and_other_clocks()
        off: dict[str, list] = {}
        for clock in others:
            _add(off, dict(clock.acct))
        return (dict(own.acct) if own is not None else {}), off

    def stage_cpu(self) -> tuple[float, float]:
        """(CPU seconds of the request thread's tiling, of the other
        threads' stages), as far as their stacks have emptied."""
        own, others = self._own_and_other_clocks()
        return (own.cpu if own is not None else 0.0,
                sum(c.cpu for c in others))

    @property
    def stages(self) -> dict[str, float]:
        """Self wall seconds by stage on the request thread."""
        return {name: a[1] for name, a in self.stage_totals()[0].items()}

    def add_leg(self, host: str, n_slices: int) -> None:
        """Record a map-reduce leg (node host + slice count) for
        /debug/queries visibility."""
        with self._mu:
            self.legs.append({"host": host, "slices": n_slices})

    def to_json(self) -> dict:
        rem = self.remaining()
        with self._mu:
            legs = list(self.legs)
        own, off = self.stage_totals()
        stages = {name: a[1] for name, a in own.items()}
        out = {
            "id": self.id,
            "pql": self.pql[:200],
            "index": self.index,
            "tenant": self.tenant,
            "lane": self.lane,
            "state": self.state,
            "remote": self.remote,
            "node": self.node,
            "startedAt": self.started_wall,
            "elapsedS": round(self.elapsed(), 4),
            "remainingS": None if rem is None else round(rem, 4),
            "legs": legs,
            "stages": {k: round(v, 4) for k, v in stages.items()},
        }
        if off:
            out["offThread"] = {k: round(a[1], 4) for k, a in off.items()}
        if self.cost is not None:
            # The accounting roll-up rides /debug/queries and the slow
            # log (obs.accounting.QueryCost.summary — totals only).
            out["cost"] = self.cost.summary()
        if self.plan is not None:
            # Cross-link only (the traceKept pattern): the fingerprint
            # keys into /debug/plans for the full tree; the decision
            # roll-up makes the slow log self-describing.
            out["planFingerprint"] = self.plan.fingerprint
            decisions = self.plan.decision_summary()
            if decisions:
                out["planDecisions"] = decisions
        if self.result_digest:
            # Replay cross-link (obs.capture): the digest is the
            # shadow-diff comparison key; captureId names the record
            # in /debug/capture/records that re-issues this query.
            out["resultDigest"] = self.result_digest
        if self.capture_id:
            out["captureId"] = self.capture_id
        return out


# -- thread-local propagation ------------------------------------------------

_tls = threading.local()

# Cross-thread view of the same bindings, for samplers that inspect
# OTHER threads (the continuous profiler tags each sampled stack with
# the query id bound to that thread — a thread-local is invisible from
# the sampler thread). Plain dict ops are atomic under the GIL.
_by_thread: dict[int, QueryContext] = {}


def current() -> Optional[QueryContext]:
    """The QueryContext bound to this thread, or None."""
    return getattr(_tls, "ctx", None)


def by_thread() -> dict[int, QueryContext]:
    """Snapshot of thread-id -> bound QueryContext, for cross-thread
    samplers (obs.profile)."""
    return dict(_by_thread)


@contextmanager
def use(ctx: Optional[QueryContext]):
    """Bind ``ctx`` as this thread's current query for the duration.
    Used by the executor around each worker leg so layers without a
    ctx argument (mesh dispatch) can still check the budget. ``None``
    is allowed (binds nothing-current, e.g. internal maintenance
    queries)."""
    prev = getattr(_tls, "ctx", None)
    _tls.ctx = ctx
    tid = threading.get_ident()
    if ctx is not None:
        _by_thread[tid] = ctx
    else:
        _by_thread.pop(tid, None)
    try:
        yield ctx
    finally:
        _tls.ctx = prev
        if prev is not None:
            _by_thread[tid] = prev
        else:
            _by_thread.pop(tid, None)


def stage(name: str, **tags):
    """``ctx.stage`` on the thread's current query; the shared no-op
    when none is bound. The form for layers that take no ctx argument
    (device dispatch, residency, the planner)."""
    ctx = getattr(_tls, "ctx", None)
    if ctx is None:
        return NOP
    return ctx.stage(name, **tags)


def span(name: str, **tags):
    """``ctx.span`` on the thread's current query; the shared no-op
    when none is bound or it has no trace."""
    ctx = getattr(_tls, "ctx", None)
    if ctx is None or ctx.trace is None:
        return NOP
    return _SpanCM(ctx, name, tags or None)


def check_current() -> None:
    """check() on the thread's current query; no-op when none bound.
    The hook the device dispatch layer calls before compiling or
    dispatching a program on behalf of a query."""
    ctx = getattr(_tls, "ctx", None)
    if ctx is not None:
        ctx.check()
