"""stall_named_pct: share of the window's quiet time whose holder the
server could name (a collector pass, a background tick); silent where
the window had no quiet interval."""

from . import _interp


def read(run):
    win = _interp.window(run)
    if win is None or win["quiet"]["wallUs"] <= 0:
        return None
    unknown = win["byHolder"].get("unknown", {}).get("wallUs", 0)
    return 100.0 * (win["quiet"]["wallUs"] - unknown) \
        / win["quiet"]["wallUs"]
