"""The sharded count program against the mesh's aggregate HBM roofline.

``kernels_roofline``'s own reading — the least bytes the answers need
(``bytes_fns``, from the query text) of the reads that a device program
answered inside the traced slice, over ONE chip's HBM peak, over
``trace.busy_s`` — divided by the configuration's ``mesh_devices``:
every slab is sharded over the slice axis and each chip reads its own
share, so the bound is that many chips' bandwidth. ``busy_s`` is the
mean of the busy seconds of the device planes that ran an operation.
Each device reads at least its share at no more than one chip's peak, so
the share cannot pass 100; a program that ran on one device of the four
reads 25 at most. ``kernels_roofline`` itself would read four times this
and is not listed for a mesh cell. Silent where it is, and where the
configuration names no ``mesh_devices``."""

from . import kernels_roofline


def read(run):
    n_dev = run.config.get("mesh_devices")
    one_chip = kernels_roofline.read(run) if n_dev else None
    return None if one_chip is None else one_chip / n_dev
