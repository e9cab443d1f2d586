"""All device work of the traced slice against the HBM roofline, the
slabs its reads built included.

``kernels_roofline`` with the cold side counted: a read's least bytes
are its leaves read once (``bytes_fns``, from the query text) PLUS the
slabs it built written once (``fill_bytes.cold_leaves``, from its
``coldLeaves``), summed over the reads that a device program answered
and whose answer was read inside the slice; the time is the device's
busy seconds in the slice, count programs and densify programs alike.
One share over all device work. Bandwidth bounds both: a fill scatters
a few thousand words into a slab it has to write whole.

A fill whose transfer was the host-dense pack + ``device_put`` writes
its slab by DMA, outside ``XLA Ops``: its bytes would be counted and
its time not. So the fill bytes are scaled by the densified share of
the window's fills, (fills - fillsDense) / fills of
``/debug/vars.deviceBlockCache``, where the program counts
``fillsDense`` (all fills count where it does not); the two counters
of the window are logged beside the reading.

Read off one trace by hand first (share8-count-zipf, seed 3400000021,
PR 34; ``trace_reduce.py``'s docstring has the planes and lines). In
3.10 s the plane ``/device:TPU:0`` carried 174 count programs on ``XLA
Modules`` (``jit_count_exprs_n1_k2`` / ``_k3`` / ``_k4``: 84 / 54 / 36
runs, 1.14 / 1.03 / 0.89 ms) and 62 runs of ``jit_densify``, one a
sparse fill, 2.87 ms of the slice's 5.93 ms busy. One fill is six
events on ``XLA Ops``, 34 us at bucket width 4, 49 us at 8, ~80 at 16:

    %copy-start / %copy-done   vals from the host's layout, async: its
                               2.6 us are on ``Async XLA Ops``, under %copy.1
    %copy.1, %copy.2           u32[32,256,G]{1,2,0} -> {2,1,0:T(8,128)}: lanes and
                               vals into the kernel's tiling, 2.6 + 2.2 us
    %densify_pallas.1          custom-call(u32[32,256,G] %copy.1, ... %copy.2),
                               custom_call_target="tpu_custom_call": the kernel,
                               19.8 us at G = 4, 34.7 at 8, 65 at 16
    %copy_bitcast_fusion       u32[32,32768] fusion(u32[4,8,256,128] %bitcast.3):
                               the kernel's tiles into the slab's shape, the 4 MiB
                               slab read and written once more, 9.5-9.7 us

The kernel's name carries its operand shapes, so it is one name a
width and the slice's ten longest operations hold three of them: a
by-name sum would drop the rest, which is why this is one share over
the union of all operations and not a share of ``densify_pallas``.
Against the 5.1 us that writing 4 MiB takes at 819 GB/s a fill is at
10-15 % of its roofline and the count programs at ~90 % of theirs; the
slice as a whole read 46.12 there. The two transfers of lanes and vals
(``device_put``, 32 x 256 x G x 8 B) are DMAs outside ``XLA Ops``, as
a host-dense fill's whole slab is.

None where the slice holds no device operation or no such read.
"""

import sys

from ..lib import bytes_fns, fill_bytes
from . import _fills


def densified_share(run) -> float:
    """(fills - fillsDense) / fills of the window; 1.0 where the
    program does not tell the two transfers apart."""
    fills = _fills.fills(run)
    dense = _fills.delta(run, "fillsDense")
    if fills is None or dense is None:
        return 1.0
    return max(0.0, (fills - dense) / fills)


def read(run):
    tr = run.trace
    if not tr or not tr.get("busy_s") or not run.peak:
        return None
    leaves = filled = 0
    for r in run.records:
        if (r.ok and not r.op.write
                and r.stats.get("devicePrograms", 0) >= 1
                and tr["t0"] <= r.done <= tr["t1"]):
            leaves += getattr(bytes_fns, r.op.cls["bytes_fn"])(
                r.op, run.config)
            filled += fill_bytes.cold_leaves(r.stats, run.config)
    if not leaves:
        return None
    share = densified_share(run)
    sys.stderr.write(
        "cellbench: cold_kernels_roofline: window fills"
        f" {_fills.delta(run, 'fills')}, fillsDense"
        f" {_fills.delta(run, 'fillsDense')}, fillBytes"
        f" {_fills.delta(run, 'fillBytes')}; slice: leaf bytes {leaves},"
        f" fill bytes {filled} x densified share {share:.4f}\n")
    least_s = (leaves + filled * share) / run.peak["hbm_bytes_per_s"]
    return 100.0 * least_s / tr["busy_s"]
