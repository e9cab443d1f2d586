"""Byte function of a residency fill: the least HBM bytes the slabs a
request built need.

Counted from the request's ``X-Pilosa-Stats`` header and the
configuration, whatever implements the fill: a slab that was not
resident has to be written to HBM once, every slice of the row as a
dense 131,072 B row (``bytes_fns.SLICE_ROW_BYTES``), before a count
program can read it. What a fill reads on the way (the sparse pairs a
densify kernel scatters, a staging copy) is the implementation's and is
not counted: a fill that moves more than the slab shows as a smaller
share of the roofline, not as a bigger denominator. A request that
waited for another request's fill (``fillWaits``) wrote nothing itself
and adds nothing here.
"""

from __future__ import annotations

from .bytes_fns import SLICE_ROW_BYTES


def cold_leaves(stats: dict, config: dict) -> int:
    """Every slab the request built, written once over every slice."""
    return (int(stats.get("coldLeaves", 0)) * int(config["n_slices"])
            * SLICE_ROW_BYTES)
