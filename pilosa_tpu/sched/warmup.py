"""Cold-start warmup: background-compile the serving program catalogue.

The first real device query otherwise pays the whole cold chain —
backend init, mesh construction, and the trace+compile of each serving
program. At server start this lane compiles
the **unified program catalogue** (parallel.programs.CATALOGUE — the
count fold, the batched multi-Count form, TopN exact + filtered, the
materializing fold, the BSI comparison circuit, and the fused
multi-op-tree program, the with-source TopN's count program) against
dummy all-zero slabs on a daemon thread.

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

The residency fill's on-device densify is warmed with them: one
program a bucket width (parallel.mesh.DENSIFY_WIDTHS) for the holder's
slab shape. Which width a fill needs depends on the row's data, so
unlike a query program it is not met by the first query of its shape
but at random, hours in (one cold read in seven windows paid 263 ms for
one; PERF.md, PR 32). A slab shape no pass warmed — an index loaded or
grown after start, a TopN candidate block — is heard of from the first
fill that densifies one (``parallel.mesh.on_densify``) and its other
widths are compiled here, on this thread, which therefore lives as
long as the server.

State is exposed at ``/status`` (``pending → running → done``;
``disabled`` when the mesh is off or unavailable, ``failed`` carries
the error) including per-program coverage: which catalogue programs
compiled, against which bucket. Gated by PILOSA_TPU_WARMUP (default
on; tests disable it the way they disable the cost model).
"""

from __future__ import annotations

import os
import queue
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
        # Slab shapes (their leading axes) whose densify programs are
        # compiled at every width, and the shapes heard of and waiting.
        self.densified: list[list] = []
        self._densify_heard: set = set()
        self._densify_todo: queue.SimpleQueue = queue.SimpleQueue()
        self._stop = threading.Event()
        self._done = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run,
                                        name="pilosa-warmup",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._densify_todo.put(None)

    def wait(self, timeout: Optional[float] = None) -> None:
        """Until the start-up pass has ended, whatever its state."""
        if self._thread is not None:
            self._done.wait(timeout)

    def to_json(self) -> dict:
        from ..parallel import programs
        catalogue = list(programs.CATALOGUE)
        return {"state": self.state, "compiled": list(self.compiled),
                "error": self.error or None,
                "bucket": self.bucket,
                "densified": list(self.densified),
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
        try:
            followed = self._pass()
            sched_context.settle()      # the compiled runtime, frozen
        finally:
            self._done.set()
        if followed:
            self._follow()

    def _hear_densify(self, shape: tuple) -> None:
        """``parallel.mesh.on_densify``: a fill's thread names the slab
        shape it densifies; a new one is queued for this lane."""
        if shape not in self._densify_heard:
            self._densify_heard.add(shape)
            self._densify_todo.put(shape)

    def _warm_densify(self, shape: tuple) -> None:
        from ..parallel import mesh as mesh_mod
        self._densify_heard.add(shape)
        with sched_context.background_tick("warmup"):
            mesh_mod.warm_densify(*shape)
        self.densified.append(list(shape[1]))

    def _follow(self) -> None:
        """After the pass: compile every width of each slab shape a
        fill densifies that no pass has warmed, until ``stop``."""
        from ..parallel import mesh as mesh_mod
        try:
            while True:
                shape = self._densify_todo.get()
                if shape is None or self._stop.is_set():
                    return
                try:
                    self._warm_densify(shape)
                except Exception as e:  # noqa: BLE001 - never kill serving
                    self.logger.printf(
                        "warmup: densify programs of %s failed: %s: %s",
                        shape[1], type(e).__name__, e)
        finally:
            if mesh_mod.on_densify == self._hear_densify:
                mesh_mod.on_densify = None

    def _pass(self) -> bool:
        """The start-up pass; True where a mesh exists and the lane
        goes on to follow the fills."""
        t0 = time.monotonic()
        self.state = "running"
        try:
            mesh = self.executor._mesh_or_none()
            if mesh is None:
                self.state = "disabled"
                return False
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
            served = self._holder_max_slices()
            self.bucket = programs.slice_bucket(served, n_dev)
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
                # A frame form of 1,024 rows and eight positions a
                # device: a real frame's shape compiles on its first read.
                "topn_src_counts": lambda: mesh_mod.topn_src_counts(
                    mesh, ("leaf", 0), (
                        mesh_mod.shard_slices(
                            mesh, np.zeros((S, 1025), np.int32)),
                        mesh_mod.shard_slices(mesh, np.zeros(
                            (mesh.shape[mesh_mod.AXIS_SLICES], 8),
                            np.int32))), [a]),
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
            mode = mesh_mod.densify_mode()
            if mode is not None:
                mesh_mod.on_densify = self._hear_densify
                # An empty holder has no slab shape yet: its first
                # fill will name it.
                if served and not self._stop.is_set():
                    self._warm_densify((mesh, (S,), WORDS_PER_SLICE // 128,
                                        mode == "interpret"))
            self.state = "done"
            self.elapsed_s = time.monotonic() - t0
            self.logger.printf(
                "warmup: compiled %s at bucket %d in %.2fs",
                ",".join(self.compiled), S, self.elapsed_s)
            return mode is not None
        except Exception as e:  # noqa: BLE001 - warmup must never kill serving
            self.state = "failed"
            self.error = f"{type(e).__name__}: {e}"
            self.elapsed_s = time.monotonic() - t0
            self.logger.printf("warmup failed: %s", self.error)
            return False
