"""cold_upload_ms: wall time of the stage upload in the window's reads,
a fill of the window (see ``_fills``, ``_stages``)."""

from . import _fills


def read(run):
    return _fills.stage_ms_a_fill(run, "upload")
