"""Window deltas of the server's interpreter block, shared by the four
``interpreter`` metrics' readers.

The server (``pilosa_tpu/sched/context.py``) says at
``/debug/vars.interpreter`` who held the one interpreter every request
thread shares: ``gc.gen<g>`` = ``{"n", "wallUs", "maxUs"}`` around every
collector pass (plus ``gc.collected``); ``quiet`` = ``{"n", "wallUs",
"thresholdMs", "byHolder", "byStage"}``, the stretches of
``thresholdMs`` and more in which no request thread crossed a stage
boundary although one was inside a stage, each named after the hold (a
collector pass, a background tick) that covers it or ``unknown``, and
after the stage that waited; ``recent`` = the last 32 of them. And at
``backgroundTicks.<loop>`` ``lateUs`` / ``lateN``: how late the loop's
thread came back from its timed wait, which is what any thread pays to
get the interpreter back after a release. A traced run reads
``/debug/vars`` before and after its window; the difference is the
window's. A program without the block reads None everywhere.
"""

from __future__ import annotations

import sys

from . import _stages

GENERATIONS = ("gen0", "gen1", "gen2")
_logged = False


def window(run) -> dict | None:
    """{"seconds", "gc": {gen: grown}, "collected", "quiet": {"n",
    "wallUs"}, "byHolder", "byStage", "late": {loop: grown}, "recent"}
    between the two reads of /debug/vars, or None where either lacks
    ``interpreter``, ``backgroundTicks`` or the reads' own clock
    (``sampledAt``)."""
    bg = _stages.background(run)     # None without the reads' clock
    if bg is None:
        return None
    ticks, seconds = bg
    b, a = run.before["vars"], run.after["vars"]
    if "interpreter" not in a or "interpreter" not in b:
        return None
    ia, ib = a["interpreter"], b["interpreter"]
    gc = {g: {"n": ia["gc"][g]["n"] - ib["gc"][g]["n"],
              "wallUs": ia["gc"][g]["wallUs"] - ib["gc"][g]["wallUs"],
              "maxUs": ia["gc"][g]["maxUs"]}
          for g in GENERATIONS}
    qa, qb = ia["quiet"], ib["quiet"]
    win = {"seconds": seconds, "gc": gc,
           "collected": ia["gc"]["collected"] - ib["gc"]["collected"],
           "quiet": {k: qa[k] - qb[k] for k in ("n", "wallUs")},
           "byHolder": _stages._delta(qa["byHolder"], qb["byHolder"]),
           "byStage": _stages._delta(qa["byStage"], qb["byStage"]),
           "late": {loop: d for loop, d in ticks.items()
                    if d.get("lateN")},
           "recent": [r for r in ia["recent"]
                      if b["sampledAt"] <= r["at"] <= a["sampledAt"]]}
    _log(win)
    return win


def _log(win: dict) -> None:
    """The whole table, once a run, on stderr: PERF.md's "Who held the
    interpreter" is copied from it."""
    global _logged
    if _logged:
        return
    _logged = True
    s = win["seconds"]
    lines = [f"cellbench: interpreter, {s:.1f} s between the reads:"
             f" collector (generation: passes, wall ms, longest ms since"
             f" start), {win['collected']} objects collected"]
    for g, d in sorted(win["gc"].items()):
        lines.append(f"cellbench:   gc.{g:5s} {d['n']:6d}"
                     f" {d['wallUs'] / 1e3:9.1f} {d['maxUs'] / 1e3:8.1f}")
    lines.append("cellbench: wake-up lateness (loop: timed waits, mean ms"
                 " late)")
    for loop, d in sorted(win["late"].items()):
        lines.append(f"cellbench:   {loop:11s} {d['lateN']:5d}"
                     f" {d['lateUs'] / d['lateN'] / 1e3:8.3f}")
    q = win["quiet"]
    lines.append(f"cellbench: quiet intervals: {q['n']},"
                 f" {q['wallUs'] / 1e3:.1f} ms (by holder / by waiting"
                 " stage: n, ms)")
    for by in ("byHolder", "byStage"):
        for name, d in sorted(win[by].items(),
                              key=lambda kv: -kv[1]["wallUs"]):
            lines.append(f"cellbench:   {by:8s} {name:13s} {d['n']:4d}"
                         f" {d['wallUs'] / 1e3:9.1f}")
    for r in sorted(win["recent"], key=lambda r: -r["ms"])[:8]:
        lines.append(f"cellbench:   recent   {r['ms']:9.1f} ms in"
                     f" {r['stage']}, holder {r['holder']}"
                     f" ({r['holderMs']:.1f} ms) at {r['at']:.3f}")
    sys.stderr.write("\n".join(lines) + "\n")
    sys.stderr.flush()
