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

Staleness is handled by keys, and a leaf slab is retired by its row. A
TopN candidate block's key embeds the owning VIEW's ``(uid,
generation)`` token (models.view.View), which every write and every
lifecycle event of the view's fragments moves: such a block stops
being referenced and ages out of the LRU. A leaf slab's key embeds the
view's uid and its ROW's token (``View.row_token``: the generation of
the last whole-view retirement and of the last write to that row), so
a write to row r retires only row r's slabs — every other row's slab
stays resident — and an import, a reopen or a corrupt file retires
the view's. Retired slabs are dropped from the cache at once (the
budget they held is free before the write is acknowledged), and a
fill that raced the retirement, its key taken before it, is not kept
(``get_or_build``'s ``owner``). The key is O(1) in the slice count.

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
from ..storage import native

# Default packed-row budget per fragment (256 rows × 128 KB = 32 MB
# host-side).
DEFAULT_MAX_ROWS = 256

# Process-wide HBM budget for device-resident blocks. v5e chips have
# ~16 GB HBM; leave headroom for the programs' own activations.
DEFAULT_HBM_BUDGET_MB = 1024

_uid_counter = itertools.count(1)

# Rows whose resident slab a write retired and no fill has built again
# yet (what ``refills`` counts), at most this many, oldest forgotten.
_REFILL_WATCH = 4096

# Fills that pack and upload at once. Their host work runs on the one
# interpreter every request shares, in stretches as long as a read's,
# so each fill in flight lengthens every resident read's turns at it.
# Two keep one fill's Python on the interpreter while another's native
# pack, transfer and densify run off it; a fill past them waits (stage
# ``fill_slot``) off the interpreter instead of queueing beside reads.
_FILL_SLOTS = threading.BoundedSemaphore(2)


class _Fill:
    """One build in flight: what its waiters are handed."""

    __slots__ = ("done", "arr", "error")

    def __init__(self):
        self.done = threading.Event()
        self.arr = None
        self.error: Optional[BaseException] = None


def _arrays(entry) -> tuple:
    """The device arrays of a cached entry: a slab or block is one, a
    frame form (``frame_positions``) is a pair."""
    return entry if isinstance(entry, tuple) else (entry,)


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
        # device_put (the others densified on the device); the builds
        # whose pack was one call into the native library.
        self.fills = 0
        self.fill_waits = 0
        self.fill_seconds = 0.0
        self.fill_bytes = 0
        self.fills_dense = 0
        self.packs_native = 0
        # The slabs one row of one view owns: view uid -> row id ->
        # their keys, and each such key's (uid, row), so that a write
        # drops its row's entries and an eviction forgets its key.
        self._owned: dict[int, dict[int, set]] = {}
        self._owner_of: dict[tuple, tuple[int, int]] = {}
        # Slabs dropped and why: a write changed a bit of their row
        # (and their bytes), or their whole view was retired; fills of
        # a row whose slab a write had dropped.
        self.retired_by_write = 0
        self.retired_by_write_bytes = 0
        self.retired_all = 0
        self.refills = 0
        self._refill_due: OrderedDict[tuple[int, int], None] = OrderedDict()
        # Whole-frame forms built (``frame_positions``), and dropped by
        # a write to their view.
        self.frame_builds = 0
        self.frames_retired = 0

    @staticmethod
    def _nbytes(arr) -> int:
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in _arrays(arr))

    def get_or_build(self, key: tuple, build: Callable[[], jax.Array],
                     owner: Optional[tuple] = None) -> jax.Array:
        """The resident array under ``key``, built on a miss by the
        first requester, on its own thread and outside the lock
        (packing + device_put take long and must not serialize
        unrelated queries). Fills are single-flight by key: a request
        that arrives while the key's build is in flight waits for it
        and takes its array (a miss, and a ``fillWait``) instead of
        packing the same rows again. The caller's key embeds the
        owning view's (or row's) token, read before the fragments are
        resolved, so a waiter never receives a slab older than its own
        token: a write between two requests gives two keys and two
        builds. A build that raises wakes its waiters with the error
        and leaves nothing behind: the next request builds again.

        ``owner`` = ``(view, row_id, token)`` marks a slab that one row
        of one view owns, ``token`` being the ``view.row_token(row_id)``
        its key embeds: it is kept only if that is still the row's
        token, checked under this cache's lock — a build whose key was
        taken before a write to its row hands its array to its waiters
        and keeps nothing — and ``retire_rows`` / ``retire_view`` drop
        it. ``row_id`` None marks the view's whole-frame form
        (``frame_positions``), whose token is ``view.frame_token()``
        and which a write to any row drops."""
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
            evicted: list = []
            with self._mu:
                del self._filling[key]
                self.fills += 1
                self.fill_seconds += time.perf_counter() - t0
                if mine.error is None:
                    self.fill_bytes += self._nbytes(mine.arr)
                    if owner is None:
                        self._insert(key, mine.arr, evicted)
                    else:
                        view, row_id, token = owner
                        if token == (view.frame_token() if row_id is None
                                     else view.row_token(row_id)):
                            self._insert(key, mine.arr, evicted,
                                         (view.uid, row_id))
            mine.done.set()
            del evicted     # freed out of the lock (see _insert)
        accounting.note_cold_leaf()
        return mine.arr

    def _insert(self, key: tuple, arr, evicted: list,
                tag: Optional[tuple[int, int]] = None) -> None:
        """Keep a built array (lock held), unless it is bigger than the
        whole working set: that one is a one-shot. ``tag`` = the
        ``(view uid, row)`` that owns it. The arrays it evicts go to
        ``evicted``, for the caller to let go of once the lock is
        released: freeing a device buffer lets go of the interpreter, and
        every read's ``lookup`` would queue on the lock behind it."""
        nbytes = self._nbytes(arr)
        if nbytes > self.budget_bytes:
            return
        if key not in self._lru:
            self._lru[key] = arr
            self.used_bytes += nbytes
            if tag is not None:
                self._owner_of[key] = tag
                self._owned.setdefault(tag[0], {}).setdefault(
                    tag[1], set()).add(key)
                if tag in self._refill_due:
                    del self._refill_due[tag]
                    self.refills += 1
        self._lru.move_to_end(key)
        # len > 1 keeps the just-built entry (now most-recent) alive.
        while self.used_bytes > self.budget_bytes and len(self._lru) > 1:
            old_key, old = self._lru.popitem(last=False)
            evicted.append(old)
            self.used_bytes -= self._nbytes(old)
            self.evictions += 1
            tag = self._owner_of.pop(old_key, None)
            if tag is not None:
                rows = self._owned[tag[0]]
                keys = rows[tag[1]]
                keys.discard(old_key)
                if not keys:
                    del rows[tag[1]]
                    if not rows:
                        del self._owned[tag[0]]

    def _drop(self, keys, dropped: list) -> int:
        """Take ``keys`` out of the LRU (lock held) into ``dropped``, to
        be let go of out of the lock (``_insert``); their bytes."""
        nbytes = 0
        for k in keys:
            del self._owner_of[k]
            arr = self._lru.pop(k)
            dropped.append(arr)
            nbytes += self._nbytes(arr)
        self.used_bytes -= nbytes
        return nbytes

    def retire_rows(self, uid: int, row_ids) -> int:
        """A write changed a bit of each of ``row_ids`` in view ``uid``,
        and the view's row tokens have moved: drop those rows' slabs
        (their keys can no longer be built). Returns how many; the
        request that wrote is told (``X-Pilosa-Stats.retiredSlabs``)."""
        n, dropped = 0, []
        with self._mu:
            rows = self._owned.get(uid)
            if rows:
                frames = rows.pop(None, None)
                if frames:
                    self.frames_retired += len(frames)
                    self._drop(frames, dropped)
                due = self._refill_due
                for r in row_ids:
                    keys = rows.pop(r, None)
                    if keys:
                        n += len(keys)
                        self.retired_by_write_bytes += self._drop(
                            keys, dropped)
                        due[(uid, r)] = None
                        due.move_to_end((uid, r))
                if not rows:
                    del self._owned[uid]
                while len(due) > _REFILL_WATCH:
                    due.popitem(last=False)
                self.retired_by_write += n
        del dropped
        if n:
            accounting.note_retired_slabs(n)
        return n

    def retire_view(self, uid: int) -> int:
        """Every row of view ``uid`` is retired (an import, a reopen, a
        corrupt file replaced): drop all of its slabs."""
        dropped: list = []
        with self._mu:
            rows = self._owned.pop(uid, None)
            if not rows:
                return 0
            n = 0
            for keys in rows.values():
                n += len(keys)
                self._drop(keys, dropped)
            self.retired_all += n
        del dropped
        return n

    def frames(self) -> tuple[int, int]:
        """(whole-frame forms resident, their bytes)."""
        with self._mu:
            keys = [k for k, (_, row) in self._owner_of.items()
                    if row is None]
            return len(keys), sum(self._nbytes(self._lru[k])
                                  for k in keys)

    def note_frame_build(self) -> None:
        with self._mu:
            self.frame_builds += 1

    def note_pack(self, dense: bool, on_native: bool) -> None:
        """The filling thread's word on its pack: whether it ships a
        host-dense block, and whether the native library packed it."""
        with self._mu:
            self.fills_dense += dense
            self.packs_native += on_native

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
            self._owned.clear()
            self._owner_of.clear()
            self._refill_due.clear()
            self.used_bytes = 0

    def snapshot(self) -> dict:
        with self._mu:
            # Where the resident bytes really are: summed over every
            # entry's shards, by device id. Slabs sharded over the slice
            # axis give each device usedBytes / n; a slab that landed
            # whole on one device, or replicated on all, shows here.
            per_device: dict[str, int] = {}
            for entry in self._lru.values():
                for shard in (s for arr in _arrays(entry)
                              for s in arr.addressable_shards):
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
                    "fillsDense": self.fills_dense,
                    "packsNative": self.packs_native,
                    "retired": {"byWrite": self.retired_by_write,
                                "byWriteBytes": self.retired_by_write_bytes,
                                "all": self.retired_all},
                    "refills": self.refills}


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
    how many the pass took, and ``native``, whether the pass was one
    call into the native library (``storage.native``)."""
    from . import mesh as mesh_mod
    mode = mesh_mod.densify_mode()
    tags["slices"] = lead_shape[0]
    with sched_context.stage("fill_slot"):
        _FILL_SLOTS.acquire()
    try:
        with sched_context.stage("pack", **tags) as pack:
            on_native = native.available()
            sparse, block, taken = packed.pack_slab(
                collect(), sparse=mode is not None)
            if sparse is None:
                block = block.reshape(lead_shape + block.shape[1:])
                tags.update(bytes=block.nbytes, path="dense")
            else:
                sparse = [a.reshape(lead_shape + a.shape[1:])
                          for a in sparse]
                tags.update(bytes=sum(a.nbytes for a in sparse),
                            path="sparse")
            device_cache().note_pack(sparse is None, on_native)
            pack.tag(containers=taken, native=on_native, **tags)
        with sched_context.stage("upload", **tags):
            if sparse is not None:
                return mesh_mod.densify_sharded(
                    mesh, *sparse, interpret=(mode == "interpret"))
            return mesh_mod.shard_slices(mesh, block)
    finally:
        _FILL_SLOTS.release()


def leaf_slab(mesh, key: tuple, frags, row_id: int,
              owner: Optional[tuple] = None) -> jax.Array:
    """Device-resident ``[bucket(n_slices), words]`` slab of one PQL
    leaf row across ``frags`` (one fragment per slice, None = absent =
    zero words; a list, or a callable that resolves it — only a miss
    needs the fragments), globally sharded over the slice axis and held
    in the budgeted HBM cache under ``key``.

    The caller owns the key contract (executor embeds the backing
    view's uid and the row's token, read BEFORE the fragments are
    resolved, and passes ``owner`` = ``(view, row_id, token)``: a write
    to the row or a reopen of the view drops the slab);
    ``_fill`` owns the transfer."""

    def build(frags=frags):
        if callable(frags):
            frags = frags()
        n = _bucketed_slices(mesh, len(frags))

        def collect():
            rows = [frag.row_containers(row_id)
                    if frag is not None else None for frag in frags]
            return rows + [None] * (n - len(rows))

        return _fill(mesh, (n,), collect, row=row_id)

    return device_cache().get_or_build(key, build, owner)


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


def _frame_layout(mesh, n_slices: int,
                  n_rows: int) -> tuple[int, int, int]:
    """(padded slices, devices on the slice axis, R) of a frame form."""
    from . import mesh as mesh_mod
    r = 1 << max(0, int(n_rows) - 1).bit_length()
    return (_bucketed_slices(mesh, n_slices),
            mesh.shape[mesh_mod.AXIS_SLICES], r)


def _positions_bucket(n: int) -> int:
    """``n`` positions rounded up to 64 steps an octave: at most ~3 %
    padding for the program to read."""
    n = max(1, n)
    step = max(1, (1 << (n - 1).bit_length()) // 64)
    return -(-n // step) * step


def frame_form_bytes(mesh, slice_bits: list[int], n_rows: int) -> int:
    """What ``frame_positions`` holds for a frame of ``n_rows`` rows
    whose slices hold ``slice_bits`` set bits each."""
    n, d, r = _frame_layout(mesh, len(slice_bits), n_rows)
    per = n // d
    most = max(sum(slice_bits[i:i + per])
               for i in range(0, n, per))
    return (n * (r + 1) + d * _positions_bucket(most)) * 4


def frame_positions(mesh, key: tuple, frags, n_rows: int,
                    owner: Optional[tuple] = None
                    ) -> tuple[jax.Array, jax.Array]:
    """A ranked frame's standard view held on the device in a form
    sized to its bits, under ``key`` in the budgeted HBM cache: two
    int32 arrays, both sharded over the mesh's slice axis as the leaf
    slabs are. ``offsets`` ``[n_slices, R + 1]`` (``n_slices``
    bucket-padded, R = ``n_rows`` rounded up to a power of two) and
    ``positions`` ``[devices, P]``: slice s, row r holds the positions
    ``positions[d, offsets[s, r]:offsets[s, r + 1]]`` of the device d
    that holds slice s, each ``local slice << 20 | column``, sorted by
    (slice, row, column), zero past the device's last offset — 4 bytes
    a set bit where a dense slab is 128 KiB a row a slice, and each
    device holds and reads only its own slices. P, the most positions
    a device holds, is rounded up to 64 steps an octave, so a frame
    that grows by a write keeps its compiled program
    (``programs.topn_src_counts_program``). ``frags`` is one fragment a
    slice (None = absent) or a callable that resolves them;
    ``owner`` = ``(view, None, view.frame_token())`` lets a write to any
    row of the view drop the form (``DeviceBlockCache.retire_rows``).
    Built once per token: positions read under each fragment's lock
    (``Fragment.positions``), the layout in numpy, one transfer."""
    from . import mesh as mesh_mod

    def build(frags=frags):
        if callable(frags):
            frags = frags()
        n, d, r = _frame_layout(mesh, len(frags), n_rows)
        per = n // d
        with sched_context.stage("pack", frame=len(frags),
                                 slices=n) as pack:
            offsets = np.empty((n, r + 1), dtype=np.int32)
            parts = [[] for _ in range(d)]
            held = [0] * d
            for si in range(n):
                dev, local = divmod(si, per)
                frag = frags[si] if si < len(frags) else None
                pos = (frag.positions() if frag is not None
                       else np.empty(0, dtype=np.uint64))
                rows = pos >> np.uint64(20)
                offsets[si] = held[dev] + np.searchsorted(
                    rows, np.arange(r + 1, dtype=np.uint64))
                parts[dev].append((np.uint64(local << 20)
                                   | (pos & np.uint64((1 << 20) - 1))
                                   ).astype(np.int32))
                held[dev] += len(pos)
            positions = np.zeros((d, _positions_bucket(max(held))),
                                 dtype=np.int32)
            for dev in range(d):
                if held[dev]:
                    positions[dev, :held[dev]] = np.concatenate(
                        parts[dev])
            nbytes = offsets.nbytes + positions.nbytes
            pack.tag(positions=sum(held), bytes=nbytes)
        with sched_context.stage("upload", frame=len(frags),
                                 bytes=nbytes):
            form = (mesh_mod.shard_slices(mesh, offsets),
                    mesh_mod.shard_slices(mesh, positions))
        device_cache().note_frame_build()
        return form

    return device_cache().get_or_build(key, build, owner)


def next_uid() -> int:
    """A process-unique id for a residency token (a fragment's
    DeviceRowCache, a View): one counter, so no two ever alias."""
    return next(_uid_counter)


class DeviceRowCache:
    """Per-fragment residency state: host packed-row LRU + the
    fragment's own (uid, generation) pair (the cluster generation map
    publishes it). ``owner`` is the fragment's View (None for a bare
    library fragment): every invalidation here retires the written rows
    of the view, or all of it (``View.retire_rows`` / ``retire_all``),
    which moves the view's token too."""

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

    # Every invalidation is called AFTER the data changed and BEFORE the
    # write is acknowledged: a reader that finds a token unmoved has
    # seen the data.

    def bump_owner(self) -> None:
        """Move the view's token alone: the storage swapped by a
        snapshot, no bit changed. Routes resolved before it are
        resolved again; no slab is retired, and the cluster generation
        map does not see it."""
        owner = self.owner
        if owner is not None:
            owner.bump()

    def reopened(self) -> None:
        """The fragment was (re)opened: whatever a reader resolved or
        built against the view while it was closed is resolved and
        built again. Its own generation stays (its bits are the file's,
        as before)."""
        owner = self.owner
        if owner is not None:
            owner.retire_all()

    def invalidate_row(self, row_id: int) -> None:
        self._host_rows.pop(row_id, None)
        self.generation += 1
        owner = self.owner
        if owner is not None:
            owner.retire_rows((row_id,))

    def invalidate_rows(self, row_ids) -> None:
        """Batch invalidation: one generation bump for the whole write
        batch, one retirement of its rows."""
        pop = self._host_rows.pop
        for rid in row_ids:
            pop(rid, None)
        self.generation += 1
        owner = self.owner
        if owner is not None:
            owner.retire_rows(row_ids)

    def invalidate_all(self) -> None:
        self._host_rows.clear()
        self.generation += 1
        owner = self.owner
        if owner is not None:
            owner.retire_all()
