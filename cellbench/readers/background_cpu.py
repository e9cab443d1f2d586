"""background_cpu_pct: CPU the server's background loops used between
the two reads of /debug/vars around the window, as a share of that
time (one core = 100)."""

from . import _stages


def read(run):
    bg = _stages.background(run)
    if bg is None:
        return None
    loops, seconds = bg
    return 100.0 * sum(d["cpuUs"] for d in loops.values()) / 1e6 / seconds
