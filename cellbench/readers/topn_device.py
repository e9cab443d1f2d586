"""Share of the window's with-source TopN reads whose counts came from
one device program (``/debug/vars.topn``)."""

from . import _topn


def read(run):
    d = _topn.delta(run)
    if d is None or not d["srcDevice"] + d["srcHost"]:
        return None
    return 100.0 * d["srcDevice"] / (d["srcDevice"] + d["srcHost"])
