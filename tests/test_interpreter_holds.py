"""Who held the interpreter (sched.context, ISSUE 36): collector passes
bracketed by a ``gc.callbacks`` hook, background ticks as named holds
that say how late they woke, and a stop of every request thread (a
*quiet interval*) named after the hold that covers it — the
``interpreter`` block of /debug/vars, the four per-layer metrics that
read it, and the ``pilosa.gc.gen<g>`` segment on the profiler's clock.
docs/OBSERVABILITY.md "`/debug/vars`: `interpreter`" is the operator's
page.

By name and on counts. The rules are tested on a planted clock (every
reading of ``time`` that ``sched.context`` makes is the test's), the
planted holds with real threads and real holds of a quarter of a second;
the one negative reading taken with real threads raises the threshold
above anything this machine's scheduler does by itself.
"""

import gc
import glob
import http.client
import importlib
import json
import os
import random
import threading
import time
from collections import deque

import pytest

from cellbench import run_cell
from pilosa_tpu.sched import context as sched_context
from pilosa_tpu.sched.context import StageClock
from pilosa_tpu.server.server import Server

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
JOIN_S = 60.0
CELLS = ["c4-count-hot", "c4-count-hot-solo", "c4-count-hot-mesh4",
         "share8-count-zipf"]
METRICS = {
    # name: (reader module, unit, better, moves)
    "gc_pause_pct": ("gc_pause", "%", "lower", "qps"),
    "wake_late_ms": ("wake_late", "ms", "lower", "read_p50_ms"),
    "stall_pct": ("stall", "%", "lower", "read_p95_ms"),
    "stall_named_pct": ("stall_named", "%", "higher", "read_p95_ms"),
}


def _interp() -> dict:
    return sched_context.stage_totals()["interpreter"]


def _ticks(loop: str) -> dict:
    return sched_context.stage_totals()["backgroundTicks"].get(
        loop, {"n": 0, "wallUs": 0, "cpuUs": 0, "lateN": 0, "lateUs": 0})


def _grown(after: dict, before: dict) -> dict:
    return {k: {f: v - before.get(k, {}).get(f, 0) for f, v in a.items()}
            for k, a in after.items()}


# -- the collector's hook -----------------------------------------------------

class TestCollectorHook:
    def test_the_hook_is_registered_once(self):
        assert gc.callbacks.count(sched_context._gc_hook) == 1

    def test_a_full_collection_counts_in_gen2(self):
        before = _interp()["gc"]
        gc.collect()
        after = _interp()["gc"]
        assert after["gen2"]["n"] - before["gen2"]["n"] >= 1
        assert after["gen2"]["wallUs"] > before["gen2"]["wallUs"]
        assert after["gen2"]["maxUs"] >= before["gen2"]["maxUs"]
        assert after["gen2"]["maxUs"] > 0

    def test_a_young_pass_counts_in_gen0(self):
        before = _interp()["gc"]
        gc.collect(0)
        after = _interp()["gc"]
        assert after["gen0"]["n"] - before["gen0"]["n"] >= 1
        assert after["gen0"]["wallUs"] >= before["gen0"]["wallUs"]

    def test_collected_grows_by_the_garbage(self):
        gc.collect()
        before = _interp()["gc"]["collected"]
        for _ in range(100):
            cycle: list = []
            cycle.append(cycle)
        del cycle
        found = gc.collect()
        assert found >= 100
        assert _interp()["gc"]["collected"] - before >= 100

    def test_a_stop_without_its_start_counts_nothing(self):
        """The hook registered in the middle of a pass sees its stop."""
        before = _interp()["gc"]
        assert sched_context._gc_open is None
        sched_context._gc_hook("stop", {"generation": 2, "collected": 5,
                                        "uncollectable": 0})
        assert _interp()["gc"] == before


# -- the rules, on a planted clock -------------------------------------------

class PlantedTime:
    """What ``sched.context`` reads of ``time``: one wall clock and one
    CPU clock a thread id, moved by the test (which plays every thread
    itself: the observers' clocks and the ticks all run on ``me``)."""

    def __init__(self):
        # Later than any real reading: the module's older marks and
        # holds lie in this clock's past.
        self.now = time.perf_counter() + 1e6
        self.me = threading.get_ident()
        self.cpu: dict[int, float] = {self.me: 0.0}

    def perf_counter(self):
        return self.now

    def time(self):
        return 1.79e9 + self.now

    def thread_time(self):
        return self.cpu[self.me]

    def pthread_getcpuclockid(self, tid):
        return tid

    def clock_gettime(self, tid):
        if tid not in self.cpu:
            raise OSError("no such thread")
        return self.cpu[tid]

    def run(self, seconds: float, tid: int = 0):
        """``seconds`` pass; thread ``tid`` (default: none) is on a CPU
        throughout."""
        self.now += seconds
        if tid:
            self.cpu[tid] = self.cpu.get(tid, 0.0) + seconds


@pytest.fixture
def planted(monkeypatch):
    clock = PlantedTime()
    monkeypatch.setattr(sched_context, "time", clock)
    # Restored afterwards: a mark or an end left in this clock's far
    # future would hide every real interval from later tests.
    monkeypatch.setattr(sched_context, "_last_any", 0.0)
    monkeypatch.setattr(sched_context, "_quiet_end", 0.0)
    monkeypatch.setattr(sched_context, "_HOLDS", deque(maxlen=64))
    monkeypatch.setattr(sched_context, "_OPEN_HOLDS", {})
    monkeypatch.setattr(sched_context, "_QUIET_RECENT", deque(maxlen=32))
    return clock


def _quiet_grown(before: dict) -> dict:
    after = _interp()["quiet"]
    return {"n": after["n"] - before["n"],
            "wallUs": after["wallUs"] - before["wallUs"],
            "byHolder": {k: v for k, v in _grown(
                after["byHolder"], before["byHolder"]).items() if v["n"]},
            "byStage": {k: v for k, v in _grown(
                after["byStage"], before["byStage"]).items() if v["n"]}}


def _idle_server_then_a_request(t):
    clock = StageClock("http_read")
    clock.switch("http_write")
    clock.close()
    t.run(0.100)                # nobody is inside a stage
    clock = StageClock("http_read")     # a clock's first boundary
    t.run(0.001)
    clock.switch("parse")
    clock.close()


def _first_push_of_a_leg_clock(t):
    StageClock("http_read")
    t.run(0.100)
    leg = StageClock()          # a pool thread's: opens on its push
    leg.push("leg")
    t.run(0.001)
    leg.pop()


def _lone_thread_in_a_10ms_stage(t):
    clock = StageClock("http_read")
    clock.push("fetch")
    t.run(0.010)
    clock.pop()
    t.run(0.024)                # just under the threshold
    clock.close()


def _a_sleeping_tick_while_the_others_cross(t):
    clocks = [StageClock("leg") for _ in range(4)]
    with sched_context.background_tick("sleeper"):
        for i in range(30):     # 300 ms, a boundary every 10
            t.run(0.010)
            clocks[i % 4].push("route")
            clocks[i % 4].pop()
    for c in clocks:
        c.close()


def _one_thread_waits_for_the_device_the_others_cross(t):
    waiter = StageClock("fetch")
    other = StageClock("leg")
    for _ in range(10):
        t.run(0.010)
        other.push("route")
        other.pop()
    waiter.close()              # 100 ms in fetch, but never quiet
    other.close()


class TestQuietIntervals:
    @pytest.mark.parametrize("body", [
        _idle_server_then_a_request, _first_push_of_a_leg_clock,
        _lone_thread_in_a_10ms_stage,
        _a_sleeping_tick_while_the_others_cross,
        _one_thread_waits_for_the_device_the_others_cross],
        ids=lambda f: f.__name__.strip("_"))
    def test_the_lower_readings_record_nothing(self, planted, body):
        before = _interp()["quiet"]
        body(planted)
        assert _quiet_grown(before)["n"] == 0
        assert _interp()["recent"] == []

    def test_a_lone_long_stage_is_one_and_nobody_is_named(self, planted):
        before = _interp()["quiet"]
        clock = StageClock("http_read")
        clock.push("fetch")
        planted.run(0.100)
        clock.pop()
        clock.close()
        grown = _quiet_grown(before)
        assert grown["n"] == 1 and grown["wallUs"] == 100000
        assert grown["byHolder"] == {"unknown": {"n": 1,
                                                 "wallUs": 100000}}
        assert grown["byStage"] == {"fetch": {"n": 1, "wallUs": 100000}}
        (rec,) = _interp()["recent"]
        assert rec["stage"] == "fetch" and rec["holder"] == "unknown"
        assert rec["ms"] == 100.0 and rec["holderMs"] == 0.0
        # the interval's START, on the clock of ``sampledAt``
        assert rec["at"] == pytest.approx(planted.time() - 0.100)

    def _stop(self, planted, hold, seconds=0.200):
        """One thread inside ``dispatch`` while ``hold(planted)`` runs
        for ``seconds``; the quiet interval's record."""
        before = _interp()["quiet"]
        clock = StageClock("http_read")
        clock.switch("dispatch")
        hold(planted)
        clock.switch("fetch")
        clock.close()
        grown = _quiet_grown(before)
        assert grown["n"] == 1, grown
        assert grown["wallUs"] == round(seconds * 1e6)
        assert list(grown["byStage"]) == ["dispatch"]
        return _interp()["recent"][-1]

    def test_a_closed_tick_is_named(self, planted):
        def hold(t):
            with sched_context.background_tick("planted"):
                t.run(0.200, t.me)
        rec = self._stop(planted, hold)
        assert rec["holder"] == "bg.planted" and rec["holderMs"] == 200.0

    def test_a_tick_still_open_is_named(self, planted):
        """Its long call has returned, its ``finally`` has not run."""
        tick = sched_context.background_tick("planted")
        tick.__enter__()
        try:
            rec = self._stop(planted, lambda t: t.run(0.200, t.me))
        finally:
            tick.__exit__(None, None, None)
        assert rec["holder"] == "bg.planted" and rec["holderMs"] == 200.0
        assert sched_context._OPEN_HOLDS == {}

    def test_a_running_collection_is_named(self, planted):
        """The first waiter may run before the hook sees ``stop``."""
        def hold(t):
            sched_context._gc_hook("start", {"generation": 2})
            t.run(0.200)
        try:
            rec = self._stop(planted, hold)
        finally:
            before = _interp()["gc"]["gen2"]["n"]
            sched_context._gc_hook("stop", {"generation": 2,
                                            "collected": 0})
        assert rec["holder"] == "gc.gen2" and rec["holderMs"] == 200.0
        assert _interp()["gc"]["gen2"]["n"] == before + 1
        assert sched_context._HOLDS[-1][2] == "gc.gen2"

    def test_a_finished_collection_is_named(self, planted):
        def hold(t):
            sched_context._gc_hook("start", {"generation": 1})
            t.run(0.200)
            sched_context._gc_hook("stop", {"generation": 1,
                                            "collected": 3})
        rec = self._stop(planted, hold)
        assert rec["holder"] == "gc.gen1" and rec["holderMs"] == 200.0

    def test_a_hold_of_under_half_the_interval_names_nobody(self, planted):
        def hold(t):
            sched_context._gc_hook("start", {"generation": 2})
            t.run(0.090)
            sched_context._gc_hook("stop", {"generation": 2,
                                            "collected": 0})
            t.run(0.110)
        rec = self._stop(planted, hold)
        assert rec["holder"] == "unknown" and rec["holderMs"] == 0.0

    def test_a_tick_asleep_names_nobody(self, planted):
        """Open across the whole stop, but its thread used no CPU: a
        tick waiting for a disk or a compile holds nothing."""
        def hold(t):
            with sched_context.background_tick("sleeper"):
                t.run(0.200)
        rec = self._stop(planted, hold)
        assert rec["holder"] == "unknown"

    def test_a_holder_the_machine_parked_is_still_named(self, planted):
        """On a CPU for 60 % of the stop (a loaded machine took the
        rest): it held the interpreter all the same."""
        def hold(t):
            with sched_context.background_tick("planted"):
                t.run(0.120, t.me)
                t.run(0.080)
        rec = self._stop(planted, hold)
        assert rec["holder"] == "bg.planted" and rec["holderMs"] == 200.0

    def test_a_tick_that_mostly_waited_names_nobody(self, planted):
        """Open across the stop with a little CPU of its own: it was
        queueing for the interpreter like the request threads."""
        def hold(t):
            with sched_context.background_tick("waiter"):
                t.run(0.060, t.me)
                t.run(0.140)
        rec = self._stop(planted, hold)
        assert rec["holder"] == "unknown"

    def test_of_two_ticks_that_cover_it_the_inner_is_named(self, planted):
        """``history`` ticks inside ``runtime``: both cover the stop,
        and the shorter hold is the tighter fit."""
        before = _interp()["quiet"]
        clock = StageClock("fetch")
        with sched_context.background_tick("outer"):
            planted.run(0.010, planted.me)
            clock.push("merge")             # the last boundary before
            with sched_context.background_tick("inner"):
                planted.run(0.200, planted.me)
            assert sched_context._OPEN_HOLDS[planted.me][1] == "bg.outer"
            clock.pop()                     # and the first after it
            planted.run(0.010, planted.me)
        clock.close()
        assert _quiet_grown(before)["byHolder"] == {
            "bg.inner": {"n": 1, "wallUs": 200000}}

    def test_a_short_hold_is_not_kept(self, planted):
        sched_context._gc_hook("start", {"generation": 0})
        planted.run(0.0005)
        sched_context._gc_hook("stop", {"generation": 0, "collected": 0})
        with sched_context.background_tick("brief"):
            planted.run(0.0005, planted.me)
        assert len(sched_context._HOLDS) == 0
        with sched_context.background_tick("brief"):
            planted.run(0.002, planted.me)
        assert [h[2] for h in sched_context._HOLDS] == ["bg.brief"]

    def test_the_same_stop_seen_by_a_second_thread_counts_once(
            self, planted):
        before = _interp()["quiet"]
        a, b = StageClock("leg"), StageClock("leg")
        planted.run(0.150)
        mark = sched_context._last_any
        a.push("route")
        # b loaded the mark before a stored it
        sched_context._last_any = mark
        b.push("route")
        a.close()
        b.close()
        assert _quiet_grown(before)["n"] == 1
        assert len(_interp()["recent"]) == 1


# -- lateness -----------------------------------------------------------------

class TestLateness:
    def test_counted_only_when_due_is_given(self, planted):
        before = _ticks("late_loop")
        with sched_context.background_tick("late_loop"):
            planted.run(0.001, planted.me)
        mid = _ticks("late_loop")
        assert mid["n"] - before["n"] == 1
        assert mid["lateN"] == before["lateN"]
        assert mid["lateUs"] == before["lateUs"]
        due = planted.perf_counter() + 0.100
        planted.run(0.103)
        with sched_context.background_tick("late_loop", due):
            planted.run(0.001, planted.me)
        after = _ticks("late_loop")
        assert after["n"] - mid["n"] == 1
        assert after["lateN"] - mid["lateN"] == 1
        assert after["lateUs"] - mid["lateUs"] == 3000

    def test_never_negative(self, planted):
        before = _ticks("early_loop")
        with sched_context.background_tick(
                "early_loop", planted.perf_counter() + 5.0):
            pass
        after = _ticks("early_loop")
        assert after["lateN"] - before["lateN"] == 1
        assert after["lateUs"] == before["lateUs"]

    def test_timed_wait_says_when_it_was_due(self):
        stop = threading.Event()
        t0 = time.perf_counter()
        due = sched_context.timed_wait(stop, 0.01)
        t1 = time.perf_counter()
        assert t0 + 0.01 <= due <= t1 + 0.01
        stop.set()
        assert sched_context.timed_wait(stop, 0.01) is None

    def test_a_fixed_interval_loop_reports_every_wake_up(self):
        """The continuous profiler's own loop: every tick follows a
        timed wait, so ``lateN`` grows with ``n``."""
        from pilosa_tpu.obs.profile import ContinuousProfiler
        before = _ticks("profile")
        prof = ContinuousProfiler(hz=100, ring=64)
        prof.start()
        try:
            deadline = time.monotonic() + 10.0
            while (_ticks("profile")["n"] - before["n"] < 3
                   and time.monotonic() < deadline):
                time.sleep(0.01)
        finally:
            prof.stop()
        after = _ticks("profile")
        assert after["n"] - before["n"] >= 3
        assert after["lateN"] - before["lateN"] == after["n"] - before["n"]
        assert after["lateUs"] >= before["lateUs"]


# -- planted holds, real threads ----------------------------------------------

class Crossers:
    """Four request threads, each inside ``leg``, crossing a boundary
    about every half millisecond."""

    def __enter__(self):
        self._stop = threading.Event()
        self._threads = [threading.Thread(target=self._cross, daemon=True)
                         for _ in range(4)]
        for t in self._threads:
            t.start()
        time.sleep(0.05)
        return self

    def _cross(self):
        clock = StageClock("leg")
        while not self._stop.is_set():
            clock.push("route")
            clock.pop()
            time.sleep(0.0005)
        clock.close()

    def __exit__(self, *exc):
        self._stop.set()
        for t in self._threads:
            t.join(timeout=JOIN_S)
        assert not any(t.is_alive() for t in self._threads)


def _until(cond) -> bool:
    deadline = time.monotonic() + JOIN_S
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.005)
    return cond()


def _named(before: dict, holder: str) -> int:
    by = _interp()["quiet"]["byHolder"]
    return (by.get(holder, {}).get("n", 0)
            - before["byHolder"].get(holder, {}).get("n", 0))


@pytest.fixture(scope="module")
def unsorted():
    rng = random.Random(36)
    return [rng.random() for _ in range(1_000_000)]


class TestPlantedHolds:
    def test_a_full_collection_over_a_large_heap_is_named(self):
        gc.collect()
        gc.disable()
        try:
            heap = [[] for _ in range(2_000_000)]
        finally:
            gc.enable()
        before = _interp()["quiet"]
        with Crossers():
            gc.collect()
            assert _until(lambda: _named(before, "gc.gen2") >= 1)
        del heap
        rec = [r for r in _interp()["recent"]
               if r["holder"] == "gc.gen2"][-1]
        assert rec["stage"] in ("leg", "route")
        assert rec["ms"] >= 25.0 and rec["holderMs"] >= rec["ms"] / 2

    def test_a_long_call_inside_a_tick_is_named_while_it_is_open(
            self, unsorted):
        """Planted again until it is seen: on a machine with more
        runnable processes than cores a holder may be parked for most
        of its own hold, and then it rightly is not named."""
        before = _interp()["quiet"]
        seen = threading.Event()

        def tick():
            while not seen.is_set():
                with sched_context.background_tick("planted"):
                    sorted(unsorted)        # one call, never lets go
                    # open until the stop has been recorded (or again)
                    seen.wait(1.0)

        def named_while_open():
            return (_named(before, "bg.planted") >= 1
                    and any(h[1] == "bg.planted" for h in
                            list(sched_context._OPEN_HOLDS.values())))

        with Crossers():
            t = threading.Thread(target=tick, daemon=True)
            t.start()
            ok = _until(named_while_open)
            seen.set()
            t.join(timeout=JOIN_S)
            assert not t.is_alive()
        assert ok
        assert _ticks("planted")["n"] >= 1

    def test_a_long_call_inside_a_tick_is_named_once_it_has_closed(
            self, unsorted):
        """A lone request thread runs the tick itself, inside a stage:
        the hold is in the ring when the boundary after it looks.
        (Planted again, at most five times, where the machine parked
        the thread for most of the call.)"""
        for _ in range(5):
            before = _interp()["quiet"]
            clock = StageClock("http_read")
            clock.switch("finish")
            with sched_context.background_tick("planted"):
                sorted(unsorted)
            assert sched_context._HOLDS[-1][2] == "bg.planted"
            clock.switch("http_write")
            clock.close()
            assert (_named(before, "bg.planted")
                    + _named(before, "unknown")) == 1
            assert _interp()["recent"][-1]["stage"] == "finish"
            if _named(before, "bg.planted") == 1:
                break
        assert _named(before, "bg.planted") == 1

    def test_the_same_call_outside_any_bracket_is_unknown(self, unsorted):
        before = _interp()["quiet"]
        with Crossers():
            sorted(unsorted)
            assert _until(lambda: _named(before, "unknown") >= 1)
        assert _named(before, "bg.planted") == 0
        assert _named(before, "gc.gen2") == 0

    def test_a_tick_that_sleeps_stops_nobody(self, monkeypatch):
        """It lets go of the interpreter and the others keep crossing.
        The threshold is raised to a quarter of a second for this one:
        a loaded machine parks a process for tens of milliseconds by
        itself, and that is not what is tested."""
        monkeypatch.setattr(sched_context, "QUIET_S", 0.25)
        for attempt in range(3):
            before = _interp()["quiet"]
            with Crossers():
                with sched_context.background_tick("sleeper"):
                    time.sleep(0.3)
                time.sleep(0.05)
            grown = _interp()["quiet"]["n"] - before["n"]
            if grown == 0:
                break
        assert grown == 0
        assert _named(before, "bg.sleeper") == 0


# -- the surface ----------------------------------------------------------------

@pytest.fixture
def server(tmp_path):
    s = Server(str(tmp_path / "s"), host="127.0.0.1:0",
               anti_entropy_interval=0, polling_interval=0)
    s.open()
    conn = http.client.HTTPConnection(s.host, timeout=30)
    try:
        yield s, conn
    finally:
        conn.close()
        s.close()


def _vars(conn) -> dict:
    conn.request("GET", "/debug/vars")
    return json.loads(conn.getresponse().read())


class TestSurface:
    def test_debug_vars_carries_the_block(self, server):
        _, conn = server
        interp = _vars(conn)["interpreter"]
        assert set(interp) == {"gc", "quiet", "recent"}
        assert set(interp["gc"]) == {"gen0", "gen1", "gen2", "collected",
                                     "frozen", "freezes", "threshold"}
        for g in ("gen0", "gen1", "gen2"):
            assert set(interp["gc"][g]) == {"n", "wallUs", "maxUs"}
        assert set(interp["quiet"]) == {"n", "wallUs", "thresholdMs",
                                        "byHolder", "byStage"}
        assert interp["quiet"]["thresholdMs"] == 25
        assert isinstance(interp["recent"], list)
        assert len(interp["recent"]) <= 32
        for rec in interp["recent"]:
            assert set(rec) == {"at", "ms", "stage", "holder", "holderMs"}

    def test_two_reads_difference_cleanly(self, server):
        _, conn = server
        before = _vars(conn)
        conn.request("POST", "/index/i", b"{}")
        conn.getresponse().read()
        gc.collect()
        with sched_context.background_tick("surface_loop",
                                           time.perf_counter()):
            pass
        after = _vars(conn)
        assert after["sampledAt"] > before["sampledAt"]
        a, b = after["interpreter"], before["interpreter"]
        for g in ("gen0", "gen1", "gen2"):
            assert a["gc"][g]["n"] >= b["gc"][g]["n"]
            assert a["gc"][g]["wallUs"] >= b["gc"][g]["wallUs"]
            assert a["gc"][g]["maxUs"] >= b["gc"][g]["maxUs"]
        assert a["gc"]["gen2"]["n"] > b["gc"]["gen2"]["n"]
        assert a["gc"]["collected"] >= b["gc"]["collected"]
        for k in ("n", "wallUs"):
            assert a["quiet"][k] >= b["quiet"][k]
        for by in ("byHolder", "byStage"):
            for name, t in b["quiet"][by].items():
                assert a["quiet"][by][name]["n"] >= t["n"]
                assert a["quiet"][by][name]["wallUs"] >= t["wallUs"]
        for loop, t in after["backgroundTicks"].items():
            assert set(t) == {"n", "wallUs", "cpuUs", "lateN", "lateUs"}
            assert t["lateN"] <= t["n"] and t["lateUs"] >= 0
        tick = after["backgroundTicks"]["surface_loop"]
        assert tick["lateN"] == tick["n"] >= 1


# -- the four readers -----------------------------------------------------------

def _surfaces(at, gen2, collected, quiet, by_holder, by_stage, recent,
              ticks, block=True) -> dict:
    v = {"sampledAt": at, "backgroundTicks": ticks}
    if block:
        v["interpreter"] = {
            "gc": {"gen0": {"n": 900, "wallUs": 45000, "maxUs": 400},
                   "gen1": {"n": 80, "wallUs": 16000, "maxUs": 900},
                   "gen2": gen2, "collected": collected},
            "quiet": {"n": quiet[0], "wallUs": quiet[1],
                      "thresholdMs": 25, "byHolder": by_holder,
                      "byStage": by_stage},
            "recent": recent}
    return {"status": {}, "vars": v}


def _tick(n, wall, cpu, late_n=0, late=0):
    return {"n": n, "wallUs": wall, "cpuUs": cpu, "lateN": late_n,
            "lateUs": late}


def _run(block=True, stops=True, late=True):
    """A 50 s window: one full pass of 120 ms, 500 young ones of 60 us;
    two stops, 120 ms (the collector's) and 80 ms (nobody's); 500
    wake-ups of the profiler 1.5 ms late, 50 of the watchdog 2.6 ms."""
    run = run_cell.Run()
    recent0 = [{"at": 990.0, "ms": 40.0, "stage": "pack",
                "holder": "unknown", "holderMs": 0.0}]
    run.before = _surfaces(
        1000.0, {"n": 3, "wallUs": 90000, "maxUs": 40000}, 70,
        (1, 40000), {"unknown": {"n": 1, "wallUs": 40000}},
        {"pack": {"n": 1, "wallUs": 40000}}, recent0,
        {"profile": _tick(100, 9000, 8000, 100, 20000),
         "watchdog": _tick(10, 900, 800, 10, 2000),
         "wal_flush": _tick(5, 500, 400)}, block)
    grown_stops = stops and 1
    run.after = _surfaces(
        1050.0, {"n": 4, "wallUs": 210000, "maxUs": 120000}, 95,
        (1 + 2 * grown_stops, 40000 + 200000 * grown_stops),
        {"unknown": {"n": 1 + grown_stops,
                     "wallUs": 40000 + 80000 * grown_stops},
         **({"gc.gen2": {"n": 1, "wallUs": 120000}} if stops else {})},
        {"pack": {"n": 1, "wallUs": 40000},
         **({"http_write": {"n": 2, "wallUs": 200000}} if stops else {})},
        recent0 + ([{"at": 1020.0, "ms": 120.0, "stage": "http_write",
                     "holder": "gc.gen2", "holderMs": 119.0},
                    {"at": 1031.0, "ms": 80.0, "stage": "http_write",
                     "holder": "unknown", "holderMs": 0.0}]
                   if stops else []),
        {"profile": (_tick(600, 59000, 48000, 600, 770000) if late
                     else _tick(600, 59000, 48000, 100, 20000)),
         "watchdog": (_tick(60, 5900, 4800, 60, 132000) if late
                      else _tick(60, 5900, 4800, 10, 2000)),
         "wal_flush": _tick(9, 900, 700)}, block)
    if block:       # the young passes' growth
        run.after["vars"]["interpreter"]["gc"]["gen0"] = {
            "n": 1400, "wallUs": 75000, "maxUs": 400}
    return run


EXPECTED = {
    "gc_pause_pct": 100.0 * (0.120 + 0.030) / 50.0,
    "wake_late_ms": (750000 + 130000) / 550 / 1e3,
    "stall_pct": 100.0 * 0.200 / 50.0,
    "stall_named_pct": 100.0 * 120000 / 200000,
}


def _reader(metric: str):
    with open(os.path.join(ROOT, "cellbench", "metrics",
                           metric + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == METRICS[metric][0]
    return importlib.import_module(
        "cellbench.readers." + spec["reader"]).read


class TestReaders:
    @pytest.mark.parametrize("metric", sorted(METRICS))
    def test_reads_the_windows_growth(self, metric):
        assert _reader(metric)(_run()) == pytest.approx(EXPECTED[metric])

    @pytest.mark.parametrize("metric", sorted(METRICS))
    def test_silent_on_a_program_without_the_block(self, metric):
        """The parent commit: no ``interpreter``, no ``lateN``."""
        parent = _run(block=False, late=False)
        for s in (parent.before, parent.after):
            for t in s["vars"]["backgroundTicks"].values():
                del t["lateN"], t["lateUs"]
        assert _reader(metric)(parent) is None
        untraced = _run()
        untraced.before = untraced.after = None
        assert _reader(metric)(untraced) is None

    def test_a_window_without_a_stop(self):
        quiet = _run(stops=False)
        assert _reader("stall_pct")(quiet) == 0.0
        assert _reader("stall_named_pct")(quiet) is None
        assert _reader("gc_pause_pct")(quiet) == pytest.approx(
            EXPECTED["gc_pause_pct"])

    def test_no_loop_reported_a_wake_up(self):
        assert _reader("wake_late_ms")(_run(late=False)) is None

    def test_the_window_keeps_its_own_recent_intervals(self):
        from cellbench.readers import _interp
        win = _interp.window(_run())
        assert [r["ms"] for r in win["recent"]] == [120.0, 80.0]
        assert win["byHolder"] == {
            "unknown": {"n": 1, "wallUs": 80000},
            "gc.gen2": {"n": 1, "wallUs": 120000}}
        assert sorted(win["late"]) == ["profile", "watchdog"]
        assert win["collected"] == 25


class TestMetricFiles:
    @pytest.mark.parametrize("metric", sorted(METRICS))
    def test_file_agrees_with_benchmark_json(self, metric):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
        (entry,) = [m for m in bench["per_layer"] if m["name"] == metric]
        with open(os.path.join(ROOT, "cellbench", "metrics",
                               metric + ".json")) as f:
            spec = json.load(f)
        reader, unit, better, moves = METRICS[metric]
        assert entry == {"name": metric, "unit": unit, "better": better,
                         "source": "program_counter",
                         "layer": "interpreter", "moves": moves,
                         "workloads": CELLS}
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert spec[key] == entry[key]
        assert spec["reader"] == reader and spec["what"]
        assert os.path.exists(os.path.join(
            ROOT, "cellbench", "readers", reader + ".py"))
        # the cells it was accepted with, each still in the benchmark
        # (a later cell is appended to the list by a benchmark change)
        cells = {w["name"] for w in bench["workloads"]}
        assert set(entry["workloads"]) <= cells


# -- the profiler's clock -------------------------------------------------------

class TestProfilerClock:
    def test_a_collection_is_a_segment_on_the_host_plane(self, tmp_path):
        """Under a profiler session a pass leaves ``pilosa.gc.gen<g>``
        on the collecting thread's /host:CPU line, beside the stages
        and the ticks."""
        import jax
        from jax.profiler import ProfileData
        jax.devices()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path / "trace"),
                                 profiler_options=opts)
        try:
            clock = StageClock("dispatch")
            gc.collect()
            gc.collect(0)
            clock.close()
        finally:
            jax.profiler.stop_trace()
        assert sched_context._gc_open is None
        path = glob.glob(os.path.join(str(tmp_path / "trace"), "plugins",
                                      "profile", "*", "*.xplane.pb"))[0]
        names = set()
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                names.update(e.name for e in line.events
                             if e.name.startswith("pilosa."))
        assert {"pilosa.gc.gen2", "pilosa.gc.gen0",
                "pilosa.dispatch"} <= names, sorted(names)
