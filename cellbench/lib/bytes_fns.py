"""Byte functions: the least HBM bytes an op class's answer needs.

Counted from the query text and the configuration, whatever program
implements the op: a change that reads fewer bytes than these cannot
give the same answer, and one that reads more wastes bandwidth, which
``kernel_roofline_pct`` then shows as a smaller share.
"""

from __future__ import annotations

SLICE_ROW_BYTES = (1 << 20) // 8    # one row of one slice, dense


def dense_leaves(op, config: dict) -> int:
    """Every leaf row read once over every slice as a dense slab."""
    return len(op.rows) * int(config["n_slices"]) * SLICE_ROW_BYTES


def none(op, config: dict) -> int:
    """An op with no device work to account (a point write)."""
    return 0
