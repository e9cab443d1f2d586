"""Candidate rows a with-source TopN's first phase gave, per read
(``/debug/vars.topn``)."""

from . import _topn


def read(run):
    d = _topn.delta(run)
    if d is None or not d["srcDevice"] + d["srcHost"]:
        return None
    return d["candidates"] / (d["srcDevice"] + d["srcHost"])
