#!/usr/bin/env python3
"""The benchmark's server child, on the CPU, with the device path taken.

    rw95_child.py [--no-retire] <control dir> <repo root> -d <data> ...

``cellbench``'s harness gives its child the environment a user's server
gets, and on a CPU that server answers from the host. This child sets,
before anything imports jax, what ``tests/test_share8_cell.py`` sets in
its own process: one CPU device meshed, the device path from one slice
on, no cost model, the sparse upload in interpret mode, and a residency
budget of 16 MiB (half the slabs of 4 slices x 64 rows). Then it is
``cellbench/lib/traced_server.py``.

``--no-retire``: a write moves the view's token and nothing else — no
row's token moves and no slab is dropped — so a slab built before a
write keeps being served after it (the control of a retirement that is
too narrow).
"""

from __future__ import annotations

import os
import sys

BUDGET_MB = 16


def main(argv: list[str]) -> int:
    no_retire = argv[:1] == ["--no-retire"]
    if no_retire:
        argv = argv[1:]
    os.environ.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        "PILOSA_TPU_MESH_MIN_SLICES": "1",
        "PILOSA_TPU_COST_MODEL": "0",
        "PILOSA_TPU_SPARSE_UPLOAD": "interpret",
        "PILOSA_TPU_HBM_BUDGET_MB": str(BUDGET_MB)})
    root = argv[1]
    sys.path.insert(0, root)
    sys.path.insert(0, os.path.join(root, "cellbench", "lib"))
    if no_retire:
        from pilosa_tpu.models.view import View

        def retire_rows(self, row_ids) -> int:
            self.bump()
            return 0

        View.retire_rows = retire_rows
    import traced_server
    return traced_server.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
