"""The with-source TopN's device work in the traced slice against the
HBM roofline: ``kernels_roofline``'s arithmetic over the reads whose
counts one device program gave (``X-Pilosa-Stats.topnSrc`` device).

The bytes are the least the answers need (the op class's byte
function, ``templates/topn_src.py`` ``topn_src_least``); the time is
the device's busy seconds in the slice."""

from ..lib import bytes_fns


def read(run):
    tr = run.trace
    if not tr or not tr.get("busy_s") or not run.peak:
        return None
    need = 0
    for r in run.records:
        if (r.ok and not r.op.write
                and r.stats.get("topnSrc") == "device"
                and tr["t0"] <= r.done <= tr["t1"]):
            need += bytes_fns.resolve(r.op.cls)(r.op, run.config)
    if not need:
        return None
    return 100.0 * need / run.peak["hbm_bytes_per_s"] / tr["busy_s"]
