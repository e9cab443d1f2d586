"""Share of the device's busy time spent in the cross-device reduction.

Seconds of the operations in ``trace.device_ops`` (the slice's ten
longest by name, each a mean over the device planes) that are the
reduction, over ``trace.busy_s`` (the union of a plane's operations, a
mean over planes): what the merge of four partial counts costs the
device beside the counting. Both are per-device means, and the
operations of one plane's ``XLA Ops`` do not overlap, so the share
cannot pass 100.

Read off one four-chip trace by hand first (c4-count-hot-mesh4, seed
2700000002, PR 27; ``trace_reduce.py``'s docstring has the one-chip
layout). Each of the four planes ``/device:TPU:0..3`` carries the same
lines as on one chip. ``XLA Modules`` has one event a program run,
named by the program (``jit_count_exprs_n1_k2(<hash>)``, ``..._k3``,
``..._k4``: 664 / 401 / 265 runs in 3.09 s). ``XLA Ops`` has four
events a run, named by their HLO text:

    %convert_reduce_fusion = s32[64]{...} fusion(u32[64,32768]{...} %param, ...)
    %shift-right-arithmetic_reduce_fusion = (s32[], s32[]) fusion(...)
    %all-reduce.2 = (s32[1,1]{...}, s32[1,1]{...}) all-reduce(s32[1,1]{...}
        %bitcast.1, s32[1,1]{...} %bitcast), channel_id=2,
        replica_groups=[1,4]<=[4], use_global_device_ids=true, ...
    %pad_add_fusion = s32[2,1]{...} fusion(s32[1,1]{...}
        %get-tuple-element.2, s32[1,1]{...} %get-tuple-element.3), ...

The counting fusion reads the device's 64 slices of each leaf; the
all-reduce merges the (hi, lo) halves over the four devices, ONE
synchronous operation a run (4.9 us each there), under one name for all
three programs; ``Async XLA Ops`` and ``TC Overlay`` are empty, so there
is no ``all-reduce-start`` / ``-done`` pair to add up (the pattern below
would take them if a compiler made them). A new line ``XLA TraceMe``
holds one ``barrier-cores`` a run (70 us each): the devices waiting for
each other BEFORE a program's operations; it is inside the module's
event and not among the operations, so neither ``busy_s`` nor this share
counts it. The host plane gains four ``py_xla_execute/<tid>`` lines, one
a device.

None where no operation of the slice is the reduction: one chip (the
program has none), a program that merges on the host, no device trace.
"""

import re

# An operation is named by its HLO text, ``%<name> = <type>
# <opcode>(<operands>), ...``: the opcode says that it is the reduction.
# An operation that only takes the reduction's result as an operand
# (``get-tuple-element(%all-reduce.2)``, the ``pad_add_fusion`` above)
# is not one.
_COLLECTIVE = re.compile(
    r"^%all-reduce[\w.\-]* = |\ball-reduce(-start|-done)?\(")


def is_collective(op_name: str) -> bool:
    return _COLLECTIVE.search(op_name) is not None


def read(run):
    tr = run.trace
    if not tr or not tr.get("busy_s"):
        return None
    seconds = [s for name, s in tr.get("device_ops") or ()
               if is_collective(name)]
    if not seconds:
        return None
    return 100.0 * sum(seconds) / tr["busy_s"]
