"""HBM working-set manager: device residency for hot fragment rows.

The reference mutates mmap'd bitmaps in place and relies on the OS page
cache plus its own row cache for hot-row reuse (fragment.go:338-367);
device arrays are immutable and HBM is smaller than the on-disk index,
so device state is an explicit, *budgeted* cache:

- ``DeviceBlockCache`` — a process-wide LRU over device-resident packed
  blocks with an HBM byte budget (PILOSA_TPU_HBM_BUDGET_MB). Entries
  are the executor's mesh leaf blocks (one [slices, words] slab per
  PQL leaf row) and the mesh TopN candidate blocks. The hot entries are exactly the rank
  cache's top rows — LRU over query use keeps that working set pinned
  while bounded eviction stops 50k-rows × many-fragments from
  exceeding HBM (SURVEY §7 hard part 2).
- ``DeviceRowCache`` — per-fragment host-side LRU of packed row words
  (feeds block builds and mesh uploads; invalidated per row by writes).

Staleness is handled by keys, not callbacks: every cached block's key
embeds the owning VIEW's ``(uid, generation)`` pair (models.view.View)
— every write-invalidation of any of the view's fragments bumps the
generation where the fragment's own generation bumps, a fragment
created, opened, closed or snapshotted bumps it too, and a view that is
dropped and recreated mints a fresh uid — so stale entries simply stop
being referenced and age out of the LRU. The key is O(1) in the slice
count; invalidation is as coarse as it was for every query over the
whole index (a write to slice s always retired every slab whose slice
set held s).

Upload layout: the globally-sharded slab builders (``leaf_slab``,
``candidate_block``) pad the slice axis to its canonical bucket
(parallel.programs.slice_bucket) before the device_put, so every
resident array already has the bucket-stable shape the program
catalogue compiles for — growing an index within a bucket re-uses both
the compiled programs AND the upload path's shapes.

Host container kinds are invisible past this layer: the extraction
feeding both the sparse and dense upload legs (ops.packed pack_slab;
pack_bitmap for a streamed block) decodes array, bitmap, AND run
containers to the same word form, so run-compressed fragments (the
memory win that lets more of the matrix fit in HBM) ride the existing
bucket-padded path with no residency-side special case.
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from collections import OrderedDict
from typing import Callable, Optional

import jax
import numpy as np

from ..obs import accounting
from ..ops import packed
from ..sched import context as sched_context

# Default packed-row budget per fragment (256 rows × 128 KB = 32 MB
# host-side).
DEFAULT_MAX_ROWS = 256

# Process-wide HBM budget for device-resident blocks. v5e chips have
# ~16 GB HBM; leave headroom for the programs' own activations.
DEFAULT_HBM_BUDGET_MB = 1024

_uid_counter = itertools.count(1)


class _Fill:
    """One build in flight: what its waiters are handed."""

    __slots__ = ("done", "arr", "error")

    def __init__(self):
        self.done = threading.Event()
        self.arr = None
        self.error: Optional[BaseException] = None


class DeviceBlockCache:
    """Budgeted process-wide LRU of device-resident arrays.

    Thread-safe. An entry larger than the whole budget is returned
    uncached (one-shot upload) rather than evicting everything else.
    """

    def __init__(self, budget_bytes: Optional[int] = None):
        if budget_bytes is None:
            budget_bytes = int(os.environ.get(
                "PILOSA_TPU_HBM_BUDGET_MB", str(DEFAULT_HBM_BUDGET_MB))
            ) << 20
        self.budget_bytes = budget_bytes
        self._mu = threading.Lock()
        self._lru: OrderedDict[tuple, jax.Array] = OrderedDict()
        self._filling: dict[tuple, _Fill] = {}
        self.used_bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        # What the misses cost: builds run, requests that waited for
        # another request's build of their key, the builders' wall
        # seconds (summed; builds of different keys overlap), the bytes
        # of the slabs built (at their uploaded, bucket-padded shape)
        # and the builds whose transfer was the host-dense pack +
        # device_put (the others densified on the device).
        self.fills = 0
        self.fill_waits = 0
        self.fill_seconds = 0.0
        self.fill_bytes = 0
        self.fills_dense = 0

    @staticmethod
    def _nbytes(arr) -> int:
        return int(np.prod(arr.shape)) * arr.dtype.itemsize

    def get_or_build(self, key: tuple,
                     build: Callable[[], jax.Array]) -> jax.Array:
        """The resident array under ``key``, built on a miss by the
        first requester, on its own thread and outside the lock
        (packing + device_put take long and must not serialize
        unrelated queries). Fills are single-flight by key: a request
        that arrives while the key's build is in flight waits for it
        and takes its array (a miss, and a ``fillWait``) instead of
        packing the same rows again. The caller's key embeds the
        owning view's token, read before the fragments are resolved,
        so a waiter never receives a slab older than its own token: a
        write between two requests gives two keys and two builds. A
        build that raises wakes its waiters with the error and leaves
        nothing behind: the next request builds again."""
        with self._mu:
            arr = self._lru.get(key)
            if arr is not None:
                self._lru.move_to_end(key)
                self.hits += 1
                return arr
            self.misses += 1
            fill = self._filling.get(key)
            if fill is not None:
                self.fill_waits += 1
            else:
                mine = self._filling[key] = _Fill()
        if fill is not None:
            accounting.note_fill_wait()
            with sched_context.stage("fill_wait"):
                fill.done.wait()
            if fill.error is not None:
                raise fill.error
            return fill.arr
        t0 = time.perf_counter()
        try:
            mine.arr = build()
        except BaseException as e:
            mine.error = e
            raise
        finally:
            with self._mu:
                del self._filling[key]
                self.fills += 1
                self.fill_seconds += time.perf_counter() - t0
                if mine.error is None:
                    self.fill_bytes += self._nbytes(mine.arr)
                    self._insert(key, mine.arr)
            mine.done.set()
        accounting.note_cold_leaf()
        return mine.arr

    def _insert(self, key: tuple, arr) -> None:
        """Keep a built array (lock held), unless it is bigger than the
        whole working set: that one is a one-shot."""
        nbytes = self._nbytes(arr)
        if nbytes > self.budget_bytes:
            return
        if key not in self._lru:
            self._lru[key] = arr
            self.used_bytes += nbytes
        self._lru.move_to_end(key)
        # len > 1 keeps the just-built entry (now most-recent) alive.
        while self.used_bytes > self.budget_bytes and len(self._lru) > 1:
            _, old = self._lru.popitem(last=False)
            self.used_bytes -= self._nbytes(old)
            self.evictions += 1

    def note_dense_fill(self) -> None:
        """A builder's word that its fill ships a host-dense block."""
        with self._mu:
            self.fills_dense += 1

    def contains(self, key: tuple) -> bool:
        """Residency probe WITHOUT touching LRU order — the routing
        cost model asks whether an upload would be needed."""
        with self._mu:
            return key in self._lru

    def lookup(self, keys: list) -> list:
        """The resident array of every key (None where absent) in ONE
        lock hold, WITHOUT touching LRU order or the hit counters: a
        leg looks its operands up before it is placed, and a leg the
        router keeps on the host must leave no trace here. What it
        finds absent is NOT what places it: a veto may depend on what
        a read of resident slabs costs, on a slab too large to be kept
        and on a streaming leg's re-pack, never on residency, or a
        host answer (which fills nothing) would veto the next read of
        the same rows again. A leg that is taken reports what it used
        with ``touch`` and fills what was absent (``get_or_build``)."""
        with self._mu:
            get = self._lru.get
            return [get(k) for k in keys]

    def touch(self, keys: list) -> None:
        """Count a hit for, and refresh the LRU position of, every key
        a taken leg served from ``lookup`` (one lock hold). A key
        evicted in between still counts: the leg held its array."""
        with self._mu:
            lru = self._lru
            for k in keys:
                if k in lru:
                    lru.move_to_end(k)
            self.hits += len(keys)

    def clear(self) -> None:
        with self._mu:
            self._lru.clear()
            self.used_bytes = 0

    def snapshot(self) -> dict:
        with self._mu:
            # Where the resident bytes really are: summed over every
            # entry's shards, by device id. Slabs sharded over the slice
            # axis give each device usedBytes / n; a slab that landed
            # whole on one device, or replicated on all, shows here.
            per_device: dict[str, int] = {}
            for arr in self._lru.values():
                for shard in arr.addressable_shards:
                    dev = str(shard.device.id)
                    per_device[dev] = (per_device.get(dev, 0)
                                       + shard.data.nbytes)
            return {"entries": len(self._lru),
                    "usedBytes": self.used_bytes,
                    "budgetBytes": self.budget_bytes,
                    "perDeviceBytes": per_device,
                    "hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions,
                    "fills": self.fills, "fillWaits": self.fill_waits,
                    "fillSeconds": round(self.fill_seconds, 6),
                    "fillBytes": self.fill_bytes,
                    "fillsDense": self.fills_dense}


_device_cache: Optional[DeviceBlockCache] = None
_device_cache_mu = threading.Lock()


def device_cache() -> DeviceBlockCache:
    """The process-wide device block cache (lazy singleton)."""
    global _device_cache
    with _device_cache_mu:
        if _device_cache is None:
            _device_cache = DeviceBlockCache()
        return _device_cache


def _bucketed_slices(mesh, n_slices: int) -> int:
    """The bucket-padded slice count an upload for ``n_slices`` uses
    (zero slices are the identity for every count/TopN reduction)."""
    from . import mesh as mesh_mod
    from . import programs
    return programs.slice_bucket(n_slices,
                                 mesh.shape[mesh_mod.AXIS_SLICES])


def slab_is_kept(mesh, n_slices: int) -> bool:
    """Whether one row's slab at ``n_slices`` fits the residency budget
    at its uploaded (bucket-padded) shape: ``get_or_build`` keeps it
    then, and its fill is paid once; a larger one is packed and shipped
    again by every query that asks for it."""
    return (_bucketed_slices(mesh, n_slices) * packed.WORDS_PER_SLICE * 4
            <= device_cache().budget_bytes)


def _fill(mesh, lead_shape: tuple, collect: Callable[[], list],
          **tags) -> jax.Array:
    """One residency fill in its two stages. ``pack``: ``collect()``'s
    containers, one entry a slice-row of ``lead_shape`` (None = absent
    = zero words), packed in one pass over the slab
    (ops.packed.pack_slab), whose gate picks the transfer — bucketed
    sparse lanes and values (far fewer bytes to pack and ship at sparse
    shapes) or a host dense block. ``upload``: the transfer, and the
    on-device densify of a sparse one. Always at the bucket-padded,
    program-stable shape ``lead_shape + (words,)``. In a kept trace
    both stages' spans carry ``tags`` (which rows), ``slices`` (the
    padded slice count), ``bytes`` (what the host hands the device) and
    ``path``: ``sparse`` or ``dense``; ``pack``'s also ``containers``,
    how many the pass took."""
    from . import mesh as mesh_mod
    mode = mesh_mod.densify_mode()
    tags["slices"] = lead_shape[0]
    with sched_context.stage("pack", **tags) as pack:
        sparse, block, taken = packed.pack_slab(
            collect(), sparse=mode is not None)
        if sparse is None:
            block = block.reshape(lead_shape + block.shape[1:])
            device_cache().note_dense_fill()
            tags.update(bytes=block.nbytes, path="dense")
        else:
            sparse = [a.reshape(lead_shape + a.shape[1:]) for a in sparse]
            tags.update(bytes=sum(a.nbytes for a in sparse), path="sparse")
        pack.tag(containers=taken, **tags)
    with sched_context.stage("upload", **tags):
        if sparse is not None:
            return mesh_mod.densify_sharded(
                mesh, *sparse, interpret=(mode == "interpret"))
        return mesh_mod.shard_slices(mesh, block)


def leaf_slab(mesh, key: tuple, frags, row_id: int) -> jax.Array:
    """Device-resident ``[bucket(n_slices), words]`` slab of one PQL
    leaf row across ``frags`` (one fragment per slice, None = absent =
    zero words; a list, or a callable that resolves it — only a miss
    needs the fragments), globally sharded over the slice axis and held
    in the budgeted HBM cache under ``key``.

    The caller owns the key contract (executor embeds the backing
    view's (uid, generation), read BEFORE the fragments are resolved,
    so writes/reopens age entries out of the LRU); ``_fill`` owns the
    transfer."""

    def build(frags=frags):
        if callable(frags):
            frags = frags()
        n = _bucketed_slices(mesh, len(frags))

        def collect():
            rows = [frag.row_containers(row_id)
                    if frag is not None else None for frag in frags]
            return rows + [None] * (n - len(rows))

        return _fill(mesh, (n,), collect, row=row_id)

    return device_cache().get_or_build(key, build)


def candidate_block(mesh, key: tuple, frags,
                    row_ids: tuple) -> jax.Array:
    """Device-resident ``[bucket(n_slices), n_rows, words]`` TopN
    candidate block (same key/staleness contract and lazy ``frags`` as
    ``leaf_slab``), bucket-padded and slice-sharded — repeat TopN
    queries skip the per-query pack + upload entirely."""

    def build(frags=frags):
        if callable(frags):
            frags = frags()
        n = _bucketed_slices(mesh, len(frags))

        def collect():
            rows: list = []
            for si in range(n):
                frag = frags[si] if si < len(frags) else None
                for rid in row_ids:
                    rows.append(None if frag is None
                                else frag.row_containers(rid))
            return rows

        return _fill(mesh, (n, len(row_ids)), collect, rows=len(row_ids))

    return device_cache().get_or_build(key, build)


def next_uid() -> int:
    """A process-unique id for a residency token (a fragment's
    DeviceRowCache, a View): one counter, so no two ever alias."""
    return next(_uid_counter)


class DeviceRowCache:
    """Per-fragment residency state: host packed-row LRU + the
    fragment's own (uid, generation) pair (the cluster generation map
    publishes it). ``owner`` is the fragment's View (None for a bare
    library fragment): every bump here bumps the view's generation too
    — the token the shared ``DeviceBlockCache``'s keys embed."""

    def __init__(self, max_rows: int = DEFAULT_MAX_ROWS):
        self.max_rows = max_rows
        self.owner = None   # View._new_fragment sets it
        # Host-side packed words, feeding the device row blocks and the
        # executor's mesh block builds (which stack rows across
        # fragments host-side before one sharded device_put).
        self._host_rows: OrderedDict[int, np.ndarray] = OrderedDict()
        # (uid, generation) is this fragment's staleness key fragment:
        # generation bumps on every write-invalidation; uid is unique
        # per DeviceRowCache instance so a reopened fragment at
        # generation 0 can never alias a prior instance's entries.
        self.uid = next_uid()
        self.generation = 0

    # -- single rows

    def host_row_words(self, storage, row_id: int) -> np.ndarray:
        """Packed host words for one row (read-only view); caches on miss.

        ``storage`` is the fragment-local roaring bitmap
        (pos = row*SLICE_WIDTH + col).
        """
        words = self._host_rows.get(row_id)
        if words is not None:
            self._host_rows.move_to_end(row_id)
            return words
        words = np.zeros(packed.WORDS_PER_SLICE, dtype=np.uint32)
        packed.pack_storage_row(storage, row_id, words)
        words.flags.writeable = False  # callers copy, never mutate
        self._host_rows[row_id] = words
        while len(self._host_rows) > self.max_rows:
            self._host_rows.popitem(last=False)
        return words

    def bump(self) -> None:
        """The fragment's resident picture may have changed. The data
        changed BEFORE this call and the write is acknowledged AFTER
        it: a reader that finds the token unmoved has seen the data."""
        self.generation += 1
        self.bump_owner()

    def bump_owner(self) -> None:
        """Move the view's token alone: the fragment's lifecycle
        (opened, storage swapped by a snapshot) without a change of
        its bits, which the cluster generation map must not see."""
        owner = self.owner
        if owner is not None:
            owner.bump()

    def invalidate_row(self, row_id: int) -> None:
        self._host_rows.pop(row_id, None)
        self.bump()

    def invalidate_rows(self, row_ids) -> None:
        """Batch invalidation: one generation bump for the whole write
        batch (the key embeds the generation, so one bump suffices)."""
        pop = self._host_rows.pop
        for rid in row_ids:
            pop(rid, None)
        self.bump()

    def invalidate_all(self) -> None:
        self._host_rows.clear()
        self.bump()
