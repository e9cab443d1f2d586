"""Share of the window's answered, device-served reads that found every
operand slab resident.

``X-Pilosa-Stats`` marks the two kinds of cold read: ``coldLeaves``
(slabs the request built itself) and ``fillWaits`` (slabs it waited for
while another request built them); a read that carries neither found
every leaf in HBM. A host-served read looks nothing up and is left out
of both sides (``device_served_pct`` counts those).

None where there is nothing to read: no device-served read in the
window, no ``deviceBlockCache.fillWaits`` on the surfaces, or a
program that never sends ``fillWaits``: its cache counted waits in the
window and no response carried the key, so a waiting read cannot be
told from a resident one."""

from . import _fills


def read(run):
    reads = [r for r in run.records if r.ok and not r.op.write
             and r.stats.get("devicePrograms", 0) >= 1]
    waits = _fills.delta(run, "fillWaits")
    if not reads or waits is None:
        return None
    if waits > 0 and not any("fillWaits" in r.stats for r in run.records):
        return None
    resident = sum(1 for r in reads if not r.stats.get("coldLeaves")
                   and not r.stats.get("fillWaits"))
    return 100.0 * resident / len(reads)
