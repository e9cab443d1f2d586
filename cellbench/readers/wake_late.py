"""wake_late_ms: mean milliseconds by which the server's fixed-interval
background loops came back late from their timed waits in the window:
what one hand-off of the interpreter costs a thread."""

from . import _interp


def read(run):
    win = _interp.window(run)
    if win is None:
        return None
    n = sum(d["lateN"] for d in win["late"].values())
    if not n:
        return None
    return sum(d["lateUs"] for d in win["late"].values()) / n / 1e3
