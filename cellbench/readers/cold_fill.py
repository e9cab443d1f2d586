"""A builder's wall milliseconds a residency fill, across the window,
from /debug/vars."""

from . import _fills


def read(run):
    fills = _fills.fills(run)
    seconds = _fills.delta(run, "fillSeconds")
    if fills is None or seconds is None:
        return None
    return 1e3 * seconds / fills
