"""All device work of the traced slice against the HBM roofline.

The bytes are the least the answers need (each op class's byte function,
from the query text), summed over the reads that a device program
answered and whose answer was read inside the slice; the time is the
device's busy seconds in the slice. Bandwidth bounds it: a Count over
bitmaps does a popcount per 32-bit word read, far under the compute
peak."""

from ..lib import bytes_fns


def read(run):
    tr = run.trace
    if not tr or not tr.get("busy_s") or not run.peak:
        return None
    need = 0
    for r in run.records:
        if (r.ok and not r.op.write
                and r.stats.get("devicePrograms", 0) >= 1
                and tr["t0"] <= r.done <= tr["t1"]):
            need += getattr(bytes_fns, r.op.cls["bytes_fn"])(
                r.op, run.config)
    if not need:
        return None
    least_s = need / run.peak["hbm_bytes_per_s"]
    return 100.0 * least_s / tr["busy_s"]
