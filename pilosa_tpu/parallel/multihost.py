"""Multi-host TPU execution: one SPMD mesh across a pod of hosts.

The reference scales across machines with HTTP remote legs + gossip
(executor.go:1001-1083, gossip/gossip.go); pilosa-tpu keeps that DCN
path for *cross-cluster* queries, and adds this layer for the case the
reference cannot express: a single TPU pod spanning several hosts (e.g.
v5e-16 = 2 hosts × 8 chips), where the slice axis shards over EVERY
chip in the pod and Count/TopN reductions ride ICI end-to-end instead
of merging per-host results over HTTP.

Design (scaling-book recipe):
- each host in the pod is one jax.distributed process; together they
  own one global ``Mesh`` over all chips (slices axis, optional rows
  axis);
- each host feeds ONLY its local shard of the leaf/candidate blocks
  (``jax.make_array_from_process_local_data``) — slice placement is
  aligned so the slices a host serves are the slices its chips hold;
- the jitted programs are the SAME ones the single-host executor uses
  (parallel.programs.count_exprs_block_program / topn_block_program):
  under SPMD every process runs the identical program and the
  reduction spans the pod.

The coordinator/membership control plane stays host-side HTTP/gossip —
metadata is not bandwidth-bound (SURVEY.md §5).

Deployment contract: a pod is ONE logical cluster node (only the pod
coordinator appears in ``cluster.hosts``; a cluster of pods lists one
coordinator per pod). Every process of the pod must enter each
collective together with identically-shaped shards — the pod-internal
query broadcast in ``parallel.pod`` drives this layer from the
Server/Executor stack: the coordinator replays each device-batched
Count/TopN as a work item to every process's ``/pod/exec`` route and
all processes enter the collective together (NOT the executor's
per-node map-reduce, which would double-count the pod-global psum if
pod hosts were also cluster nodes).

Environment contract (set by the pod launcher):
  PILOSA_TPU_DIST_COORDINATOR  host:port of process 0
  PILOSA_TPU_DIST_NUM_PROCS    total process count
  PILOSA_TPU_DIST_PROC_ID      this process's id (0-based)
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from . import mesh as mesh_mod
from . import programs

_initialized = False


def initialize_from_env() -> bool:
    """Join the pod's jax.distributed job if the env contract is set.

    Idempotent; returns True when running as part of a multi-process
    job (including a degenerate 1-process one, which is how tests
    exercise this path without pod hardware).
    """
    global _initialized
    if _initialized:
        return True
    coord = os.environ.get("PILOSA_TPU_DIST_COORDINATOR")
    if not coord:
        return False
    # CPU-pod support (tests and TPU-less staging): give each process N
    # virtual CPU devices and gloo cross-process collectives. Must be
    # configured before the first backend touch.
    cpu_devs = os.environ.get("PILOSA_TPU_DIST_CPU_DEVICES")
    if cpu_devs:
        jax.config.update("jax_num_cpu_devices", int(cpu_devs))
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coord,
        num_processes=int(os.environ.get("PILOSA_TPU_DIST_NUM_PROCS", "1")),
        process_id=int(os.environ.get("PILOSA_TPU_DIST_PROC_ID", "0")))
    _initialized = True
    return True


def pod_mesh(rows: int = 1) -> Mesh:
    """A (rows × slices) mesh over every chip in the pod (all processes)."""
    return mesh_mod.make_mesh(len(jax.devices()), rows=rows)


def process_slice_range(n_slices: int) -> tuple[int, int]:
    """[lo, hi) rows of the global slice axis this process must feed.

    The global block is sharded evenly over the slice axis; with
    process-local device order matching mesh order (the default
    make_mesh layout), each process feeds a contiguous range. Slice
    placement in the cluster layer should assign these slices to this
    host so packing is local (no cross-host reads).
    """
    n_procs = jax.process_count()
    if n_slices % n_procs:
        raise ValueError(f"{n_slices} slices not divisible by"
                         f" {n_procs} processes (pad first)")
    per = n_slices // n_procs
    pid = jax.process_index()
    return pid * per, (pid + 1) * per


# Local slice-axis chunk size: every process uses the same bound, so
# chunk boundaries agree pod-wide; the global per-chunk slice count
# (chunk × n_procs, plus per-device padding ≤ n_devices) stays within
# the int32 hi/lo split (mesh.slice_chunk_bound).
def _local_chunk() -> int:
    return max(1, ((1 << 15) - len(jax.devices()))
               // jax.process_count())


def _assert_uniform_shards(*dims: int) -> None:
    """Every process must enter the chunk loops with identically-sized
    local shards — unequal shards execute different numbers of
    collectives and deadlock the pod. One tiny allgather per call
    (entered by all processes together) catches the mismatch up front.
    """
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils
    mine = np.asarray(dims, dtype=np.int64)
    everyone = np.asarray(multihost_utils.process_allgather(mine))
    if not (everyone == mine[None, :]).all():
        raise ValueError(
            "pod shard shapes differ across processes:"
            f" {everyone.tolist()} — every process must pass the same"
            " local slice/row counts (pad with zero slices)")


def _pad_local(local: np.ndarray, axis: int) -> np.ndarray:
    """Pad this process's shard to its canonical slice BUCKET
    (parallel.programs.slice_bucket over the per-process device count),
    so every process contributes the same number of slice rows per
    device AND the assembled global array has a bucket-stable shape —
    the pod reuses one compiled program as the index grows within a
    bucket, exactly like the single-host path. Zero slices are the
    identity for every count/TopN reduction, so the result is exact
    even though the zeros interleave between process ranges in the
    global order. Deterministic from the shard length alone, so every
    process picks the same bucket (the shard-uniformity allgather has
    already pinned the lengths equal)."""
    per_dev = len(jax.devices()) // jax.process_count()
    target = programs.slice_bucket(local.shape[axis], per_dev)
    # The GLOBAL row count (target × n_procs) must stay within the
    # int32 hi/lo split; past the cap fall back to plain device-
    # multiple padding (the chunk loops bound the shard anyway).
    if target * jax.process_count() > (1 << 15):
        n = local.shape[axis]
        target = (n + (-n % per_dev)) or per_dev
    if local.shape[axis] == target:
        return local
    pad = [(0, 0)] * local.ndim
    pad[axis] = (0, target - local.shape[axis])
    return np.pad(local, pad)


def _global_from_local(mesh: Mesh, local: np.ndarray,
                       axis: int) -> jax.Array:
    """Assemble the pod-global sharded array from this process's shard."""
    spec = [None] * local.ndim
    spec[axis] = mesh_mod.AXIS_SLICES
    sharding = NamedSharding(mesh, P(*spec))
    global_shape = list(local.shape)
    global_shape[axis] = local.shape[axis] * jax.process_count()
    return jax.make_array_from_process_local_data(
        sharding, local, tuple(global_shape))


def count_expr(mesh: Mesh, expr: tuple, local_leaves: np.ndarray) -> int:
    """Pod-wide Count: each process passes its local [L, S_local, W]
    leaf shard; the psum spans every chip on every host. Chunks the
    slice axis identically on every process (int32 hi/lo bound).
    The K=1 form of count_exprs."""
    return count_exprs(mesh, (expr,), local_leaves)[0]


def count_exprs(mesh: Mesh, exprs: tuple,
                local_leaves: np.ndarray) -> list[int]:
    """Pod-wide batched Counts: K expressions over one shared local
    leaf shard, one collective program per chunk (the pod form of
    mesh.count_exprs_sharded — K counts, one dispatch)."""
    _assert_uniform_shards(*local_leaves.shape, len(exprs))
    fn = programs.count_exprs_block_program(mesh, tuple(exprs))
    totals = [0] * len(exprs)
    step = _local_chunk()
    for off in range(0, max(local_leaves.shape[1], 1), step):
        chunk = _pad_local(local_leaves[:, off:off + step], 1)
        arr = _global_from_local(mesh, chunk, 1)
        counts = mesh_mod.hilo_combine(fn(arr))  # [2, K]: one fetch
        for k in range(len(exprs)):
            totals[k] += counts[k]
    return totals


def topn_exact(mesh: Mesh, expr, local_rows: np.ndarray,
               local_leaves: Optional[np.ndarray], threshold: int = 1,
               tanimoto: int = 0) -> list[int]:
    """Pod-wide TopN exact counts: local shards in, global counts out.
    threshold>1 / tanimoto engage the per-slice pruning program
    (programs.topn_block_program) — masks are per-slice, so shard-local
    evaluation composes exactly.

    Chunks slices (int32 bound) and candidate rows (device-block byte
    budget, mirroring mesh.topn_exact) with pod-wide identical bounds.
    """
    import functools

    import jax.numpy as jnp
    n_local, n_rows, n_words = local_rows.shape
    _assert_uniform_shards(n_local, n_rows, n_words, threshold, tanimoto)
    if local_leaves is None:
        local_leaves = np.zeros((0, n_local, 1), dtype=np.uint32)
    filtered = threshold > 1 or tanimoto > 0
    if filtered:
        threshold = min(threshold, 2**31 - 1)  # counts never exceed 2^31
        fn = functools.partial(
            programs.topn_block_program(mesh, expr, filtered=True),
            jnp.int32(threshold), jnp.int32(tanimoto))
    else:
        fn = programs.topn_block_program(mesh, expr, filtered=False)
    s_step = _local_chunk()
    r_step = max(1, mesh_mod.TOPN_BLOCK_BYTES
                 // (max(s_step, 1) * n_words * 4))
    totals = [0] * n_rows
    for s_off in range(0, max(n_local, 1), s_step):
        for r_off in range(0, n_rows, r_step):
            rc = _pad_local(
                local_rows[s_off:s_off + s_step, r_off:r_off + r_step], 0)
            lc = _pad_local(local_leaves[:, s_off:s_off + s_step], 1)
            rows = _global_from_local(mesh, rc, 0)
            leaves = _global_from_local(mesh, lc, 1)
            counts = mesh_mod.hilo_combine(fn(rows, leaves))  # 1 fetch
            for r in range(rc.shape[1]):
                totals[r_off + r] += counts[r]
    return totals
