"""Share of the window's residency fills that rebuilt a slab a write
had retired, from /debug/vars.

``deviceBlockCache.refills`` counts the fills of a row whose earlier
slab a write took out of the cache; ``fills`` counts every fill. None
where either counter is absent (a program without ``refills``) or the
window filled nothing."""

from . import _fills


def read(run):
    fills = _fills.fills(run)
    refills = _fills.delta(run, "refills")
    if fills is None or refills is None:
        return None
    return 100.0 * refills / fills
