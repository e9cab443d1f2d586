"""stall_pct: share of the time between the two reads of /debug/vars
around the window in which every request thread of the server stood
still (its quiet intervals)."""

from . import _interp


def read(run):
    win = _interp.window(run)
    if win is None:
        return None
    return 100.0 * win["quiet"]["wallUs"] / 1e6 / win["seconds"]
