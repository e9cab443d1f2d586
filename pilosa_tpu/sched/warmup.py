"""Cold-start warmup: background-compile the serving program catalogue.

The first real device query otherwise pays the whole cold chain —
backend init, mesh construction, and the trace+compile of each serving
program. At server start this lane compiles
the **unified program catalogue** (parallel.programs.CATALOGUE — the
count fold, the batched multi-Count form, TopN exact + filtered, the
materializing fold, the BSI comparison circuit, and the fused
multi-op-tree program) against dummy all-zero slabs on a daemon
thread.

Shapes are keyed by the holder's ACTUAL max-slice bucket at fragment
load (parallel.programs.slice_bucket over the open indexes), not a
hardcoded device-count shape: every query whose slice count lands in
the same bucket — which is every query until the index doubles past
it — hits the warmed compilation. Combined with the persistent XLA
compile cache (mesh.arm_compile_cache) the warm path is a disk read,
and the first device query after restart stops paying seconds.

XLA compiles are shape-keyed, so an unusual query shape (an unseen
candidate-row count, a new expression structure) can still compile
later — the warmup removes the dominant cold cost, not every possible
trace.

State is exposed at ``/status`` (``pending → running → done``;
``disabled`` when the mesh is off or unavailable, ``failed`` carries
the error) including per-program coverage: which catalogue programs
compiled, against which bucket. Gated by PILOSA_TPU_WARMUP (default
on; tests disable it the way they disable the cost model).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional

from . import context as sched_context


def warmup_enabled() -> bool:
    return os.environ.get("PILOSA_TPU_WARMUP", "1") != "0"


class Warmup:
    """Compile the serving program catalogue on a background thread."""

    def __init__(self, executor, logger=None):
        from ..utils import logger as logger_mod
        self.executor = executor
        self.logger = logger or logger_mod.NOP
        self.state = "pending"
        self.error = ""
        self.compiled: list[str] = []
        self.bucket: Optional[int] = None
        self.elapsed_s: Optional[float] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run,
                                        name="pilosa-warmup",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()

    def wait(self, timeout: Optional[float] = None) -> None:
        if self._thread is not None:
            self._thread.join(timeout)

    def to_json(self) -> dict:
        from ..parallel import programs
        catalogue = list(programs.CATALOGUE)
        return {"state": self.state, "compiled": list(self.compiled),
                "error": self.error or None,
                "bucket": self.bucket,
                "coverage": {
                    "warmed": len(self.compiled),
                    "programs": len(catalogue),
                    "missing": [p for p in catalogue
                                if p not in self.compiled]},
                "elapsedS": (round(self.elapsed_s, 3)
                             if self.elapsed_s is not None else None)}

    def _holder_max_slices(self) -> int:
        """Slice count the open holder actually serves (max over
        indexes of max_slice+1) — what the first real queries will
        fan out over."""
        n = 0
        holder = getattr(self.executor, "holder", None)
        if holder is None:
            return n
        try:
            for idx in dict(holder.indexes).values():
                n = max(n, idx.max_slice() + 1)
        except Exception:  # noqa: BLE001 - holder may be mid-open
            pass
        return n

    # -- worker --------------------------------------------------------------

    def _run(self) -> None:
        t0 = time.monotonic()
        self.state = "running"
        try:
            mesh = self.executor._mesh_or_none()
            if mesh is None:
                self.state = "disabled"
                return
            # Routing constants are measured here, on the device the
            # programs below compile for, so the planner's placement
            # and the executor's veto price the first query from
            # measurements rather than sit out until it arrives.
            self.executor.calibrate(mesh)
            import numpy as np

            from ..ops.packed import WORDS_PER_SLICE
            from ..parallel import mesh as mesh_mod
            from ..parallel import programs
            n_dev = mesh.shape[mesh_mod.AXIS_SLICES]
            self.bucket = programs.slice_bucket(
                self._holder_max_slices(), n_dev)
            S = self.bucket

            def slab():
                return mesh_mod.shard_slices(
                    mesh, np.zeros((S, WORDS_PER_SLICE), np.uint32))

            a, b = slab(), slab()
            rows = None

            def rows_block():
                nonlocal rows
                if rows is None:
                    rows = mesh_mod.shard_slices(
                        mesh, np.zeros((S, 4, WORDS_PER_SLICE),
                                       np.uint32))
                return rows

            steps = {
                "count_fold": lambda: mesh_mod.count_expr_sharded(
                    mesh, ("and", ("leaf", 0), ("leaf", 1)), [a, b]),
                "count_batch": lambda: mesh_mod.count_exprs_sharded(
                    mesh, (("leaf", 0),
                           ("and", ("leaf", 0), ("leaf", 1))), [a, b]),
                "topn_exact": lambda: mesh_mod.topn_exact_sharded(
                    mesh, ("leaf", 0), rows_block(), [a]),
                "topn_filtered": lambda: mesh_mod.topn_filtered_sharded(
                    mesh, ("leaf", 0), rows_block(), [a], threshold=2),
                "topn_topk": lambda: mesh_mod.topn_topk_sharded(
                    mesh, None, rows_block(), [], k=2),
                "materialize": lambda: mesh_mod.materialize_expr_sharded(
                    mesh, ("or", ("leaf", 0), ("leaf", 1)), [a, b]),
                "bsi_compare_select": lambda: mesh_mod.bsi_range_sharded(
                    mesh, "<", 5, 8,
                    [a] + [slab() for _ in range(8)]),
                "fused_tree": lambda: mesh_mod.fused_tree_sharded(
                    mesh, (("and", ("leaf", 0), ("leaf", 1)),),
                    [(("leaf", 0), 4)], [a, b], [rows_block()]),
            }
            for name in programs.CATALOGUE:
                if self._stop.is_set():
                    break
                step = steps.get(name)
                if step is None:
                    continue
                with sched_context.background_tick("warmup"):
                    step()
                self.compiled.append(name)
            self.state = "done"
            self.elapsed_s = time.monotonic() - t0
            self.logger.printf(
                "warmup: compiled %s at bucket %d in %.2fs",
                ",".join(self.compiled), S, self.elapsed_s)
        except Exception as e:  # noqa: BLE001 - warmup must never kill serving
            self.state = "failed"
            self.error = f"{type(e).__name__}: {e}"
            self.elapsed_s = time.monotonic() - t0
            self.logger.printf("warmup failed: %s", self.error)
