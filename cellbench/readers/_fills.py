"""Window deltas of the residency cache's fill counters, shared by the
cold path's readers.

``/debug/vars.deviceBlockCache`` counts ``fills`` (builds run),
``fillWaits`` (requests that waited for another request's build),
``fillSeconds`` (the builders' wall seconds, summed) and, where the
program has them, ``fillBytes`` and ``fillsDense`` (fills whose
transfer was the host-dense pack + ``device_put``; the others densified
on the device). A traced run reads the surface after the warm requests
and after the window, and nothing else fills in between, so a
difference is the window's. Every function is silent (None) where a
counter is absent on either side: a program without it.
"""

from __future__ import annotations

from . import _stages


def delta(run, key: str):
    """Growth of ``deviceBlockCache[key]`` across the window."""
    if run.before is None or run.after is None:
        return None
    b = (run.before["vars"].get("deviceBlockCache") or {}).get(key)
    a = (run.after["vars"].get("deviceBlockCache") or {}).get(key)
    if a is None or b is None:
        return None
    return a - b


def fills(run):
    """The window's fills, where it had any."""
    n = delta(run, "fills")
    return n if n is not None and n > 0 else None


def stage_ms_a_fill(run, stage: str):
    """Wall milliseconds of ``stage`` in the window's reads, divided by
    the fills of the window (``_stages.wall_ms`` is a mean a read:
    times the reads gives the stage's whole wall)."""
    n = fills(run)
    win = _stages.window(run)
    if n is None or win is None:
        return None
    return (_stages.wall_ms(win, (stage,)) * win["requests"]) / n
