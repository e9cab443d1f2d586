"""gc_pause_pct: share of the time between the two reads of /debug/vars
around the window that the server spent inside collector passes, all
generations (a pass holds the interpreter from start to stop)."""

from . import _interp


def read(run):
    win = _interp.window(run)
    if win is None:
        return None
    us = sum(d["wallUs"] for d in win["gc"].values())
    return 100.0 * us / 1e6 / win["seconds"]
