"""Window deltas of the server's stage clock, shared by the stage
metrics' readers.

The server (``pilosa_tpu/sched/context.py``) folds every served
non-remote ``/query`` request's self-time stages into
``/debug/vars.queryStages.<lane>`` once the response is on the socket:
``{"requests": n, "cpuUs", "offThreadCpuUs", "stages": {name: {"n",
"wallUs"}}, "offThread": {...}}`` — ``stages`` are the connection
thread's (they tile the request from ``recv`` to ``sendall``; ``cpuUs``
is that thread's CPU over the tiling), ``offThread`` the map-reduce
leg's on its pool thread. A traced run reads ``/debug/vars``
before and after its window, the warm-up ends before the first read and
the read-back starts after the second, so the difference is the
window's requests and nothing else: every reader here is silent (None)
unless the ``read`` lane's ``requests`` grew by exactly the window's
answered reads. A program without the clock has no ``queryStages`` and
reads None everywhere.
"""

from __future__ import annotations

import sys

LANE = "read"
_logged = False


def _lane(surfaces) -> dict | None:
    if surfaces is None:
        return None
    return (surfaces["vars"].get("queryStages") or {}).get(LANE)


def _delta(after: dict, before: dict) -> dict:
    """{name: its counters' growth across the window}."""
    out = {}
    for name, a in after.items():
        b = before.get(name) or {}
        d = {k: v - b.get(k, 0) for k, v in a.items()}
        if d["n"] or d["wallUs"]:
            out[name] = d
    return out


def window(run) -> dict | None:
    """{"requests", "cpuUs", "offThreadCpuUs", "stages", "offThread"}
    of the window's reads, or None where the surfaces lack
    ``queryStages`` or the count of requests folded in between is not
    the count of answered reads."""
    before, after = _lane(run.before), _lane(run.after)
    if before is None or after is None:
        return None
    reads = sum(1 for r in run.records if r.ok and not r.op.write)
    grown = after["requests"] - before["requests"]
    if not reads or grown != reads:
        return None
    win = {"requests": grown,
           "cpuUs": after["cpuUs"] - before["cpuUs"],
           "offThreadCpuUs": (after["offThreadCpuUs"]
                              - before["offThreadCpuUs"]),
           "stages": _delta(after["stages"], before["stages"]),
           "offThread": _delta(after.get("offThread") or {},
                               before.get("offThread") or {})}
    _log(run, win)
    return win


def wall_ms(win: dict, names, off_thread: bool = True) -> float:
    """Mean wall milliseconds a read spent in the named stages,
    wherever they ran (``off_thread``) or on the connection thread
    alone."""
    us = sum(win["stages"].get(n, {}).get("wallUs", 0) for n in names)
    if off_thread:
        us += sum(win["offThread"].get(n, {}).get("wallUs", 0)
                  for n in names)
    return us / win["requests"] / 1e3


def per_read_ms(run, names) -> float | None:
    win = window(run)
    return None if win is None else wall_ms(win, names)


def background(run) -> tuple[dict, float] | None:
    """({loop: grown {"n", "wallUs", "cpuUs"}}, seconds between the two
    reads of /debug/vars), or None without ``backgroundTicks`` or the
    reads' own clock (``sampledAt``)."""
    if run.before is None or run.after is None:
        return None
    b, a = run.before["vars"], run.after["vars"]
    if ("backgroundTicks" not in a or "backgroundTicks" not in b
            or "sampledAt" not in a or "sampledAt" not in b):
        return None
    seconds = a["sampledAt"] - b["sampledAt"]
    if seconds <= 0:
        return None
    return _delta(a["backgroundTicks"], b["backgroundTicks"]), seconds


def _log(run, win: dict) -> None:
    """The whole self-time table, once a run, on stderr: PERF.md's
    "Where the time goes" is copied from it."""
    global _logged
    if _logged:
        return
    _logged = True
    n = win["requests"]
    lines = [f"cellbench: stage clock, {n} reads of the window: cpu ms a"
             f" read {win['cpuUs'] / n / 1e3:.3f} on the connection"
             f" thread, {win['offThreadCpuUs'] / n / 1e3:.3f} off it"
             " (stage: entries a read, wall ms a read)"]
    for where in ("stages", "offThread"):
        for name, d in sorted(win[where].items(),
                              key=lambda kv: -kv[1]["wallUs"]):
            lines.append(
                f"cellbench:   {where:9s} {name:11s} {d['n'] / n:6.2f}"
                f" {d['wallUs'] / n / 1e3:8.3f}")
    bg = background(run)
    if bg is not None:
        loops, seconds = bg
        lines.append(f"cellbench: background ticks in {seconds:.1f} s"
                     " (loop: ticks, wall ms, cpu ms)")
        for name, d in sorted(loops.items(),
                              key=lambda kv: -kv[1]["cpuUs"]):
            lines.append(f"cellbench:   {name:11s} {d['n']:5d}"
                         f" {d['wallUs'] / 1e3:9.1f} {d['cpuUs'] / 1e3:9.1f}")
    lines.append("cellbench: compileLog: " + repr(
        run.after["vars"].get("compileLog")))
    sys.stderr.write("\n".join(lines) + "\n")
    sys.stderr.flush()
