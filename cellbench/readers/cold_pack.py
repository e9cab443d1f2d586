"""cold_pack_ms: wall time of the stage pack in the window's reads, a
fill of the window (see ``_fills``, ``_stages``)."""

from . import _fills


def read(run):
    return _fills.stage_ms_a_fill(run, "pack")
