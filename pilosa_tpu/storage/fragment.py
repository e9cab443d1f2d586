"""Fragment: the storage unit at one (frame, view, slice) intersection.

Reference: fragment.go. A fragment owns one file-backed roaring bitmap
holding a rows × 2^20-column block; bit position
``pos = row * SLICE_WIDTH + (col % SLICE_WIDTH)`` (fragment.go:1511-1514).

Durability model (identical to the reference):
- data file = roaring snapshot + appended op-log (WAL); ops replay on open
- every mutation appends an op; after MAX_OP_N ops the file is atomically
  rewritten (temp + rename) and remapped (fragment.go:63-65,991-1057)
- TopN cache ids are checkpointed to a ``.cache`` protobuf sidecar
  (fragment.go:1067-1093)

TPU-first departures:
- TopN candidate ranking reads numpy rank arrays straight off the caches
  and src intersection counts come from ONE vectorized pass over the
  fragment (cached per src × mutation epoch), then the reference's
  sequential heap/threshold semantics (fragment.go:490-625) replay over
  the precomputed counts — same results, no per-row walks. Device
  serving of TopN (cross-slice batched exact counts, HBM-resident
  candidate blocks) lives at the executor layer under the calibrated
  cost model (executor._topn_exact_resident).
- block checksums hash vectorized position spans (numpy → sha1) instead of
  iterator walks; MergeBlock consensus is a vectorized multiset vote.
"""

from __future__ import annotations

import fcntl
import hashlib
import heapq
import math
import mmap
import os
import threading
import time
import zlib
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from .. import SLICE_WIDTH
from ..errors import PilosaError
from ..fault import failpoints as _fp
from ..obs import accounting as _accounting
from ..obs import metrics as obs_metrics
from ..parallel.residency import DeviceRowCache
from ..proto import internal_pb2 as pb
from ..sched import context as sched_context
from ..utils import logger as logger_mod
from ..utils import arrays as arrays_mod
from ..utils.arrays import sort_dedupe
from ..utils.streams import CappedReader
from . import cache as cache_mod
from . import integrity as integrity_mod
from . import roaring
from . import wal as wal_mod
from .bitmap import Bitmap
from .cache import Pair

# Number of operations before a snapshot rewrite (reference
# fragment.go:63-65). The reference default was 2000, sized for an era
# when every op paid its own write() and replay was a scalar walk; with
# the group-committed WAL (appends are buffered memcpy, one leader
# write per batch) and the vectorized replay lane, a 50 K-record log
# (~650 KB) reopens in milliseconds while the snapshot freeze —
# measured at ~15 ms of table patching per trigger — stops eating the
# per-op write budget 25× as often. Env-overridable so longevity
# harnesses can force snapshot storms (benchmarks/soak.py) without
# patching the module.
MAX_OP_N = int(os.environ.get("PILOSA_TPU_MAX_OP_N", "50000"))

# Replay-cost weight of one bulk-import blob position relative to one
# discrete op record: a blob's single add-run replays through the
# vectorized add_many lane (~0.06 us/bit) where mixed discrete tails
# pay the scalar/small-run walk (~1 us/op), so a blob bit contributes
# ~1/16th the reopen-replay pressure MAX_OP_N exists to bound.
_BLOB_OP_WEIGHT = 16

# Rows per checksum block (reference fragment.go:59).
HASH_BLOCK_SIZE = 100

# Run the cardinality-adaptive container-representation pass
# (roaring.Bitmap.optimize — array/bitmap/run selection per the Roaring
# papers) after bulk imports. On by default; settable off to pin the
# two-kind vintage behavior for comparisons.
_RUN_OPTIMIZE = os.environ.get("PILOSA_TPU_RUN_CONTAINERS", "1") != "0"


@dataclass
class TopOptions:
    """Options for Fragment.top (reference fragment.go TopOptions)."""
    n: int = 0
    src: Optional[Bitmap] = None
    row_ids: list[int] = field(default_factory=list)
    filter_field: str = ""
    filter_values: list = field(default_factory=list)
    min_threshold: int = 0
    tanimoto_threshold: int = 0


@dataclass
class PairSet:
    """Parallel row/column id arrays (reference fragment.go PairSet)."""
    row_ids: np.ndarray
    column_ids: np.ndarray

    @staticmethod
    def empty() -> "PairSet":
        z = np.empty(0, dtype=np.uint64)
        return PairSet(z, z)


# Matched-position budget before folding src-count hits into a
# partial (ids, counts) map; module-level so tests can shrink it to
# exercise the multi-partial merge.
_SRC_FOLD_POSITIONS = 1 << 20

# Fragments at or under this many set bits take the all-positions
# vectorized src-count pass (8 B/bit peak -> <=128 MB); bigger ones
# keep the bounded chunked walk.
_SRC_VECTOR_BITS = 16 << 20

# Largest position vector kept resident per fragment (8 B each ->
# 32 MB); larger ones are rebuilt per pass instead of pinned.
_POSITIONS_CACHE_BITS = 4 << 20

# Entries kept in the incremental per-row count map before a reset
# (bounds memory on fragments with millions of distinct rows).
_ROW_COUNT_CAP = 1 << 16

# Snapshots between full close/remap cycles (see Fragment.snapshot).
_REMAP_EVERY = 16

# Largest bulk import the WAL-first lane holds as op records (13 B per
# position) before falling back to the vintage detach-then-snapshot
# contract — bounds transient log growth between snapshot cadences.
_WAL_IMPORT_MAX_BYTES = 32 << 20


class Fragment:
    def __init__(self, path: str, index: str, frame: str, view: str,
                 slice: int, cache_type: str = cache_mod.DEFAULT_CACHE_TYPE,
                 cache_size: int = cache_mod.DEFAULT_CACHE_SIZE,
                 row_attr_store=None, stats=None, logger=logger_mod.NOP,
                 quarantine=None):
        self.logger = logger
        self.path = path
        self.index = index
        self.frame = frame
        self.view = view
        self.slice = slice
        self.cache_type = cache_type
        self.cache_size = cache_size
        self.row_attr_store = row_attr_store

        # Storage integrity (storage.integrity): the holder-level
        # quarantine registry (None for bare library fragments), the
        # quarantine flag gating the READ path, and the lazy
        # first-read verification latch (armed on every open of a
        # footered snapshot; one attr check on the read hot path).
        self.quarantine = quarantine
        self.quarantined = False
        self.quarantine_reason = ""
        self._verify_pending = False

        # Tiered storage (pilosa_tpu.tier): the TierManager hook (None
        # for bare library fragments — one attr check on the read hot
        # path keeps the gate free when tiering is off), the residency
        # state ("hot" | "cold" | "blob"), and — while cold — the set
        # of container-block indices not yet faulted in. Cold
        # fragments hold their full checksummed file on local disk;
        # reads fault exactly the blocks they touch, verifying each
        # against the footer's per-block crc table (the block map).
        # Blob fragments hold only a ``<path>.blob`` stub; the first
        # gated read fetches + verifies the file back from the blob
        # store and re-enters cold.
        self.tier = None
        self.tier_state = "hot"
        self._cold_pending: Optional[set] = None

        self.storage: Optional[roaring.Bitmap] = None
        self.cache = None                       # rank/lru count cache
        self._cache_flushed = None              # last flush_cache blob
        self.row_cache = cache_mod.SimpleCache()
        self.device = DeviceRowCache()
        self.checksums: dict[int, bytes] = {}
        self.stats = stats
        # src-TopN count maps, keyed by src-content hash, valid for one
        # mutation epoch (both TopN phases and repeat queries reuse
        # the one O(fragment bits) pass). Value: (epoch, (ids, counts)).
        self._src_counts: dict[
            bytes, tuple[int, tuple[np.ndarray, np.ndarray]]] = {}
        # Incremental per-row bit counts: single-bit mutations adjust by
        # +-1 instead of recounting the row (a full row_count walk costs
        # ~85 us vs ~1 us here — it was more than a third of the whole
        # SetBit path). Entries are exact post-mutation counts; absent
        # rows fall back to one row_count. Reset on bulk rewrites.
        self._row_counts: dict[int, int] = {}
        self._epoch = 0
        self._snapshot_n = 0
        # True once the count cache provably covers every present row
        # (set by _repair_cache_completeness on open; mutations maintain
        # coverage, LRU eviction is gated by consumers on len>=max).
        self._cache_complete = False

        # Group-commit WAL wrapper around the data file (storage.wal):
        # mutation paths APPEND records (no syscall); commit barriers
        # (wal_barrier / the serving layer's barrier_all before ack)
        # flush batches with one write()+fsync-per-policy. None when
        # PILOSA_TPU_WAL_GROUP=0 (the vintage write-through path).
        self._wal: Optional[wal_mod.GroupCommitWal] = None

        self._mu = threading.RLock()
        # Snapshot lifecycle lock. Ordering rule: ALWAYS acquired
        # BEFORE _mu when blocking (sync snapshot, close, restore);
        # the per-op async trigger — which runs UNDER _mu — only
        # try-acquires and skips when busy, so the order cannot
        # invert. Held by the background worker for its whole run
        # (released cross-thread in its finally), so "with _snap_mu"
        # doubles as the join barrier.
        self._snap_mu = threading.Lock()
        self._file = None
        self._mmap: Optional[mmap.mmap] = None
        self._open = False

    # -- lifecycle -----------------------------------------------------------

    @property
    def cache_path(self) -> str:
        return self.path + ".cache"

    def open(self) -> None:
        with self._mu:
            if self._open:
                return
            os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
            # The one-crossing mutate extension (built once, cached):
            # serving fragments are where the per-op path runs hot.
            from . import native_ext
            native_ext.load()
            self.cache = cache_mod.new_cache(self.cache_type, self.cache_size)
            stub = self.path + ".blob"
            if os.path.exists(stub):
                if os.path.exists(self.path):
                    # Crash between blob fetch-replace and stub
                    # removal: the DATA FILE WINS — it was verified
                    # before the os.replace, while a re-fetch could
                    # fail. Drop the stale stub and open normally.
                    try:
                        os.remove(stub)
                    except OSError:
                        pass
                else:
                    # Blob-tier fragment: no local bytes, no storage.
                    # The first gated read fetches + verifies the file
                    # back through the tier manager; ungated access
                    # fails loudly (storage is None) — never a guess.
                    self.tier_state = "blob"
                    self.storage = None
                    self._open = True
                    self.device.reopened()
                    return
            self._open_storage_quarantining(verify=True)
            if not self.quarantined and os.path.exists(
                    self.path + ".corrupt"):
                # A prior quarantine replaced the data file with a
                # fresh one and the process died before repair
                # completed — the aside file is the crash-safe
                # sentinel. Without it a restart would serve the
                # near-empty replacement as authoritative (a silent
                # wrong answer, the one thing this subsystem exists
                # to prevent). The repairer removes the sentinel when
                # the replica re-stream verifies clean.
                self._set_quarantined(
                    "pending repair (restart before repair completed)",
                    site="open")
            self._open_cache()
            self._open = True
            # (Re)opened: whatever a reader resolved or built against
            # the view while this fragment was closed is done again.
            self.device.reopened()

    def _open_storage_quarantining(self, verify: bool = False) -> None:
        """_open_storage, but a file whose bytes contradict their
        checksums (or no longer parse at all) QUARANTINES the fragment
        instead of bricking the open: the corrupt file moves aside
        (``<path>.corrupt`` — forensics + ``check --deep``), a fresh
        empty snapshot takes its place so writes keep buffering
        through the WAL, reads fail over to a replica (executor
        consults the registry), and the repairer re-streams the
        content (docs/FAULT_TOLERANCE.md)."""
        try:
            self._open_storage(verify=verify)
        except (ValueError, integrity_mod.CorruptionError) as e:
            self.logger.printf(
                "fragment: CORRUPT storage %s/%s/%s/%d: %s — "
                "quarantining", self.index, self.frame, self.view,
                self.slice, e)
            self._close_storage()
            try:
                os.replace(self.path, self.path + ".corrupt")
            except FileNotFoundError:
                pass
            self._open_storage()
            self._set_quarantined(f"open: {e}", site="open")
            self.device.invalidate_all()

    def _open_storage(self, verify: bool = False) -> None:
        # Open (creating) the data file, flock it, seed empty files with an
        # empty snapshot header, map, replay snapshot + op-log, then attach
        # the op writer for subsequent mutations (reference
        # fragment.go:179-234).
        # buffering=0: each op record hits the OS immediately — a WAL that
        # lingers in a userspace buffer is not a WAL.
        # ``storage.read`` failpoint: the deterministic injection site
        # for on-disk corruption (corrupt mode flips real bits in the
        # file before it is read back — fault.failpoints).
        if _fp.ACTIVE is not None:
            _fp.ACTIVE.hit("storage.read", path=self.path)
        self._file = open(self.path, "a+b", buffering=0)
        fcntl.flock(self._file.fileno(), fcntl.LOCK_EX | fcntl.LOCK_NB)
        self._file.seek(0, os.SEEK_END)
        if self._file.tell() == 0:
            # Seed with a footered empty snapshot so integrity
            # coverage starts at file birth.
            roaring.Bitmap().write_to(self._file, footer=True)
        self._mmap = mmap.mmap(self._file.fileno(), 0, prot=mmap.PROT_READ)
        self.storage = roaring.Bitmap.unmarshal(self._mmap, mapped=True,
                                                tolerate_torn_tail=True,
                                                verify_body=verify)
        if self.storage.torn_bytes:
            # Crash mid-append left a partial op record (or a torn
            # footer); the WAL is append-only so the tail is the only
            # casualty — trim it.
            size = self._file.seek(0, os.SEEK_END)
            self.storage.unmap()
            self._mmap = None
            os.ftruncate(self._file.fileno(), size - self.storage.torn_bytes)
            self._file.seek(0, os.SEEK_END)
            self._mmap = mmap.mmap(self._file.fileno(), 0,
                                   prot=mmap.PROT_READ)
            self.storage = roaring.Bitmap.unmarshal(self._mmap, mapped=True,
                                                    verify_body=verify)
        # Arm the lazy per-block verification: the first READ after an
        # open re-checks every container block's crc against the mmap
        # (the first-fault re-verification the footer exists for);
        # until then only the footer/header crcs have been checked.
        self._verify_pending = self.storage.footer is not None
        if wal_mod.group_enabled():
            self._wal = wal_mod.GroupCommitWal(self._file)
            self.storage.op_writer = self._wal
        else:
            self._wal = None
            self.storage.op_writer = self._file

    def _open_cache(self) -> None:
        # Re-rank persisted ids with counts from storage
        # (reference fragment.go:236-274).
        ids = []
        try:
            with open(self.cache_path, "rb") as f:
                ids = pb.Cache.FromString(f.read()).IDs
        except FileNotFoundError:
            pass
        except Exception:
            # The cache is advisory and reconstructible; a corrupt sidecar
            # (e.g. torn by a crash) must not brick the fragment.
            pass
        for rid in ids:
            self.cache.bulk_add(rid, self.row_count(rid))
        self._repair_cache_completeness()
        self.cache.recalculate()

    def _repair_cache_completeness(self) -> None:
        """The sidecar lags reality by up to one flush interval: rows
        first written after the last flush exist in the replayed WAL
        but not in the persisted id list, so after a crash the count
        cache silently misses them (review r5 — the single-pass TopN
        sums only cache entries, and would under-rank those rows).
        Detect by cardinality (exact per-row counts must sum to the
        storage total), repair from present_rows when the fragment is
        small enough to dump positions, else leave the cache flagged
        incomplete — consumers needing completeness (the single-pass
        TopN leg) then fall back to the recounting path."""
        total = self.storage.count()
        cached = 0
        if hasattr(self.cache, "_od"):
            cached = sum(self.cache._od.values())
        elif hasattr(self.cache, "entries"):
            cached = sum(self.cache.entries.values())
        if cached == total:
            self._cache_complete = True
            return
        if total <= _POSITIONS_CACHE_BITS:
            present = self.present_rows()
            if present is not None:
                for rid in present.tolist():
                    self.cache.bulk_add(rid, self.row_count(rid))
                self._cache_complete = True
                return
        self._cache_complete = False

    # -- storage integrity (storage.integrity; docs/FAULT_TOLERANCE.md) ------

    def _set_quarantined(self, reason: str, site: str) -> None:
        """Mark this fragment's local copy untrustworthy: the executor
        stops serving its slice locally (reads fail over through the
        breaker-ordered placement), anti-entropy stops letting it
        vote, and the repairer re-streams it from a replica. Writes
        keep applying (WAL-buffered) — they also fan out to every
        replica owner, so the repaired copy includes them."""
        obs_metrics.STORAGE_CORRUPTION.labels(site).inc()
        self.quarantined = True
        self.quarantine_reason = reason
        # Tail sampling: the query that tripped over the corruption is
        # keep-worthy evidence (obs.sampler reason "corruption").
        from ..sched import context as sched_context
        ctx = sched_context.current()
        if ctx is not None:
            ctx.note_flag("corruption")
        if self.quarantine is not None:
            if self.quarantine.add(self, reason):
                obs_metrics.STORAGE_QUARANTINED.inc()
            obs_metrics.STORAGE_QUARANTINED_LIVE.set(
                len(self.quarantine))
        else:
            obs_metrics.STORAGE_QUARANTINED.inc()
        self.logger.printf(
            "fragment: quarantined %s/%s/%s/%d (%s): %s", self.index,
            self.frame, self.view, self.slice, site, reason)

    def clear_quarantine(self) -> None:
        """Repair complete: the local copy is trustworthy again. The
        ``.corrupt`` aside file goes too — it doubles as the crash-safe
        quarantine sentinel (see open()), so leaving it would
        re-quarantine a REPAIRED fragment at the next restart."""
        try:
            os.remove(self.path + ".corrupt")
        except FileNotFoundError:
            pass
        except OSError as e:
            # A lingering sentinel re-quarantines this fragment at
            # every restart (and re-streams it for nothing) — say so
            # loudly instead of hiding the why.
            self.logger.printf(
                "fragment: could not remove quarantine sentinel"
                " %s.corrupt (%s) — the fragment will re-quarantine"
                " at the next restart until it is removed",
                self.path, e)
        self.quarantined = False
        self.quarantine_reason = ""
        if self.quarantine is not None:
            self.quarantine.remove(self)
            obs_metrics.STORAGE_QUARANTINED_LIVE.set(
                len(self.quarantine))

    def _verify_on_read(self) -> None:
        """Lazy per-block verification on the FIRST read after an open
        (the mmap-fault half of the footer contract): one crc pass over
        the container blocks against the footer table, then free. A
        mismatch quarantines and raises — the executor re-maps the
        slice onto a healthy replica (the same machinery as a failed
        remote leg)."""
        if not self._verify_pending:
            return
        self._verify_pending = False
        storage = self.storage
        info = getattr(storage, "footer", None)
        mm = self._mmap
        if info is None or mm is None:
            return
        bad = integrity_mod.verify_blocks(mm, info)
        obs_metrics.STORAGE_SCRUB_BLOCKS.labels("read").inc(
            info.block_n)
        if bad:
            self._set_quarantined(
                f"container block crc mismatch (blocks {bad[:4]},"
                f" {len(bad)} total)", site="read")
            raise integrity_mod.CorruptionError(
                f"fragment {self.index}/{self.frame}/{self.view}/"
                f"{self.slice}: {len(bad)} container blocks fail crc")

    # -- tiered storage (pilosa_tpu.tier; docs/STORAGE.md) --------------------

    def demote_cold(self) -> int:
        """Demote this fragment to the cold tier: WAL barrier, fold
        any op-log tail into a fresh checksummed snapshot, flush the
        TopN cache sidecar, then reopen metadata-only — header parsed,
        footer attached, NO container block read or verified. Returns
        the cold file's byte size (0 = demotion didn't apply: already
        cold, quarantined, torn WAL, or a footerless legacy file).
        OSError (ENOSPC mid-snapshot) propagates — the old file stays
        the record and the fragment stays hot."""
        with self._snap_mu:
            with self._mu:
                if (not self._open or self.quarantined
                        or self.tier_state != "hot"
                        or self.storage is None):
                    return 0
                try:
                    self.wal_barrier()
                except wal_mod.WalError:
                    return 0  # torn pending tail: not a clean point
                if (self.storage.op_n > 0
                        or self.storage.footer is None):
                    # Fold the op-log (and footer vintage files) into
                    # a clean footered snapshot — the cold format IS
                    # the PR-15 snapshot format, nothing new on disk.
                    self._snapshot_locked(reason="tier")
                self.flush_cache()
                self._close_storage()
                self._open_storage_quarantining()
                if self.quarantined:
                    return 0
                info = getattr(self.storage, "footer", None)
                if info is None or info.offsets is None:
                    return 0  # stay hot; nothing to fault against
                # The per-block fault gate supersedes the whole-file
                # first-read verify — each block's crc is checked as
                # it faults in instead.
                self._verify_pending = False
                self._cold_pending = set(range(info.block_n))
                self.tier_state = "cold"
                # Drop every derived cache: they hold materialized row
                # data whose residency the demotion exists to reclaim.
                self._epoch += 1
                self.row_cache.clear()
                self.device.invalidate_all()
                self.checksums.clear()
                self._src_counts.clear()
                self._cache_complete = False
                self.cache = cache_mod.new_cache(self.cache_type,
                                                 self.cache_size)
                return os.path.getsize(self.path)

    def tier_rechill(self) -> bool:
        """Reset a cold fragment's fault set (watermark eviction of a
        cold scan's residency): every block goes back to unfaulted and
        re-verifies on its next touch. Cheap — no file I/O."""
        with self._mu:
            if self.tier_state != "cold" or self.storage is None:
                return False
            info = getattr(self.storage, "footer", None)
            if info is None:
                return False
            self._cold_pending = set(range(info.block_n))
            self.row_cache.clear()
            self.device.invalidate_all()
            self._positions = None
            self._present_rows = None
            return True

    def promote(self, trigger: str = "prefetch") -> None:
        """Fully promote to hot (prefetcher / operator action). Blob
        fragments fetch first; cold fragments fault every remaining
        block (each crc-verified) and re-rank the TopN cache."""
        with self._mu:
            if not self._open or self.tier_state == "hot":
                return
            if self.tier_state == "blob":
                self._tier_fetch_locked()
            self._tier_promote_locked(trigger)

    def _tier_gate(self, row_id=None, row_ids=None, full=False,
                   write=False) -> None:
        """The read-path tier gate (caller holds _mu). Hot: stamp the
        ledger and return. Blob: fetch the file back (ColdFetchError
        on failure — the executor degrades, never guesses). Cold:
        fault exactly the container blocks covering the touched
        row(s); whole-fragment reads and writes promote fully."""
        st = self.tier_state
        if st != "hot":
            if st == "blob":
                self._tier_fetch_locked()
            if full or (row_id is None and row_ids is None):
                self._tier_promote_locked("write" if write
                                          else "read")
            else:
                idxs = self._tier_blocks_for(
                    [row_id] if row_ids is None else row_ids)
                self._fault_blocks_locked(idxs)
                if not self._cold_pending:
                    self._tier_promote_locked("read")
        if self.tier is not None:
            self.tier.on_access(self)

    def _tier_blocks_for(self, row_ids) -> list[int]:
        """Pending container-block indices covering ``row_ids``. Block
        i of the footer table is container i in file/key order, and a
        row spans exactly SLICE_WIDTH/65536 consecutive container
        keys — so the block map is two binary searches per row over
        the sorted key array (no container is touched)."""
        pending = self._cold_pending
        if not pending:
            return []
        keys = self.storage._keys_np()
        shift = (SLICE_WIDTH // 65536).bit_length() - 1
        out: set = set()
        for rid in row_ids:
            rid = int(rid)
            lo = int(np.searchsorted(keys, rid << shift, side="left"))
            hi = int(np.searchsorted(keys, (rid + 1) << shift,
                                     side="left"))
            out.update(i for i in range(lo, hi) if i in pending)
        return sorted(out)

    def _fault_blocks_locked(self, idxs) -> None:
        """Fault container blocks in: verify each block's bytes
        against the footer's crc table, then mark it resident. A
        mismatch quarantines (same contract as _verify_on_read) —
        cold data re-verifies on the way back in, so bit rot that
        happened while the fragment slept cannot reach a result."""
        if not idxs:
            return
        t0 = time.perf_counter()
        info = self.storage.footer
        offs, sizes, crcs = info.offsets, info.sizes, info.crcs
        if _fp.ACTIVE is not None:
            # Corrupt mode flips real bits in the file; the PROT_READ
            # MAP_SHARED mmap sees them, so the crc check below is the
            # real detection path, not a simulation. The span confines
            # flips to a block this fault will verify — detection is
            # guaranteed, not a draw against the whole file.
            first = idxs[0]
            _fp.ACTIVE.hit("tier.fault", path=self.path,
                           span=(int(offs[first]), int(sizes[first])))
        mm = self._mmap
        mv = memoryview(mm)
        bad: list[int] = []
        nbytes = 0
        for i in idxs:
            off, size = int(offs[i]), int(sizes[i])
            if (zlib.crc32(mv[off:off + size]) & 0xFFFFFFFF) \
                    != int(crcs[i]):
                bad.append(i)
            nbytes += size
        del mv
        obs_metrics.STORAGE_SCRUB_BLOCKS.labels("read").inc(len(idxs))
        if bad:
            obs_metrics.TIER_FAULTS.labels("corrupt").inc()
            self._set_quarantined(
                f"cold block fault crc mismatch (blocks {bad[:4]},"
                f" {len(bad)} total)", site="read")
            raise integrity_mod.CorruptionError(
                f"fragment {self.index}/{self.frame}/{self.view}/"
                f"{self.slice}: {len(bad)} cold blocks fail crc on"
                f" fault-in")
        self._cold_pending.difference_update(idxs)
        obs_metrics.TIER_FAULTS.labels("ok").inc()
        obs_metrics.TIER_FAULT_SECONDS.observe(
            time.perf_counter() - t0)
        if self.tier is not None:
            self.tier.note_fault(self, nbytes)

    def _tier_promote_locked(self, trigger: str) -> None:
        """Finish promotion to hot (caller holds _mu): fault whatever
        is still pending, then rebuild the TopN rank cache from the
        sidecar — top() on a promoted fragment must rank exactly like
        one that never left."""
        pending = self._cold_pending
        if pending:
            self._fault_blocks_locked(sorted(pending))
        self._cold_pending = None
        self.tier_state = "hot"
        self._open_cache()
        obs_metrics.TIER_PROMOTIONS.labels(trigger).inc()
        if self.tier is not None:
            try:
                size = os.path.getsize(self.path)
            except OSError:
                size = 0
            self.tier.note_promoted(self, size, trigger)

    def _tier_fetch_locked(self) -> None:
        """Materialize a blob-tier fragment back onto local disk
        (caller holds _mu): the manager fetches + verifies the
        reassembled file, we reopen it metadata-only and land in the
        cold tier (the read that triggered this then faults just the
        blocks it needs). No manager/store → ColdFetchError."""
        from ..tier.manager import ColdFetchError
        if self.tier is None:
            raise ColdFetchError(
                f"fragment {self.index}/{self.frame}/{self.view}/"
                f"{self.slice}: blob-tier but no tier manager")
        self.tier.fetch_blob(self)
        self._open_storage_quarantining()
        if self.quarantined:
            raise integrity_mod.CorruptionError(
                f"fragment {self.index}/{self.frame}/{self.view}/"
                f"{self.slice}: fetched blob data failed"
                f" verification")
        info = getattr(self.storage, "footer", None)
        self._verify_pending = False
        self.tier_state = "cold"
        self._cold_pending = (set(range(info.block_n))
                              if info is not None
                              and info.offsets is not None else set())
        try:
            size = os.path.getsize(self.path)
        except OSError:
            size = 0
        self.tier.note_fetched(self, size)

    def verify_on_disk(self) -> dict:
        """Re-read the data FILE and verify footer + blocks + WAL tail
        — the scrubber's per-fragment pass (storage.scrub). Opens its
        own fd (os.replace swaps pin the old inode, so the read is a
        consistent append-only prefix — the fragment backup trick),
        sizes it under the fragment lock after a commit barrier, and
        quarantines on any corruption verdict."""
        from . import scrub as scrub_mod
        try:
            self.wal_barrier()
        except wal_mod.WalError:
            pass  # torn pending tail: the flushed prefix still verifies
        if _fp.ACTIVE is not None:
            # The scrub leg's deterministic corruption injection site.
            _fp.ACTIVE.hit("storage.read", path=self.path)
        try:
            f = open(self.path, "rb")
        except OSError as e:
            return {"error": f"unreadable: {e}", "coverage": "none"}
        try:
            with self._mu:
                size = os.fstat(f.fileno()).st_size
            mm = mmap.mmap(f.fileno(), 0, prot=mmap.PROT_READ)
            try:
                mv = memoryview(mm)
                try:
                    verdict = scrub_mod.scrub_buffer(mv[:size])
                finally:
                    del mv
            finally:
                mm.close()
        finally:
            f.close()
        if verdict.get("corrupt"):
            self._set_quarantined(
                f"scrub: {verdict.get('error', 'checksum mismatch')}",
                site="scrub")
        return verdict

    def reset_for_repair(self) -> None:
        """Drop the suspect local state ahead of a replica re-stream
        (server.repair): the data file moves aside to ``.corrupt``, a
        fresh footered empty snapshot takes its place, and every
        derived cache resets. Writes racing this land in the fresh
        WAL; reads stay quarantined until the repairer verifies the
        streamed copy and clears the flag. Lock order: _snap_mu (waits
        out any background snapshot worker) then _mu — the
        close/restore discipline."""
        with self._snap_mu, self._mu:
            if not self._open:
                return
            self._close_storage()
            try:
                aside = self.path + ".corrupt"
                if os.path.exists(aside):
                    # An open-time quarantine already moved the original
                    # corrupt bytes aside; keep THAT forensics file.
                    os.remove(self.path)
                else:
                    os.replace(self.path, aside)
            except FileNotFoundError:
                pass
            self._open_storage()
            self._epoch += 1
            self._row_counts.clear()
            self.row_cache.clear()
            self.device.invalidate_all()
            self.checksums.clear()
            self._src_counts.clear()
            self._cache_complete = False
            self.cache = cache_mod.new_cache(self.cache_type,
                                             self.cache_size)

    def close(self) -> None:
        # _snap_mu first (lock order): waits out any worker and blocks
        # new ones for the whole close — the TOCTOU where a writer
        # spawns a worker between a join and the lock acquisition
        # would let the worker swap files on a closed fragment.
        with self._snap_mu:
            self._close_locked()

    def _close_locked(self) -> None:
        with self._mu:
            if not self._open:
                return
            self.flush_cache()
            self._close_storage()
            self.device.invalidate_all()
            self._open = False

    def _close_storage(self) -> None:
        if self._wal is not None:
            # Orderly close = commit barrier: whatever a library caller
            # appended without barriering is durable per policy before
            # the fd goes away.
            try:
                self._wal.barrier()
            except wal_mod.WalError:
                pass  # torn log: reopen trims to the flushed prefix
            self._wal.close()
            self._wal = None
        if self.storage is not None:
            self.storage.op_writer = None
        # Do NOT mmap.close() and do NOT copy containers out
        # (storage.unmap): row-cache entries and escaped query results
        # share zero-copy container views into the map, and those views
        # PIN the mapping — dropping our references lets the OS unmap
        # when the last view is GC'd, while an eager copy-out pays a
        # whole-fragment heap copy (100 MB+ per restore/snapshot of a
        # large slice, measured) for data that is about to be garbage.
        # The old inode stays valid under os.replace, so mapped views
        # never go stale. The fd closes immediately (the mapping
        # outlives it) — but NOT the flock: see the explicit unlock
        # below.
        self._mmap = None
        self._row_counts.clear()
        self.row_cache.clear()
        if self._file is not None:
            # Release the flock EXPLICITLY: mmap dups the fd, and a dup
            # shares the open file description — so while any container
            # view keeps the old map alive, close() alone would leave
            # the lock held and block the next open of this path.
            try:
                fcntl.flock(self._file.fileno(), fcntl.LOCK_UN)
            except OSError:
                pass
            self._file.close()
            self._file = None

    # -- position / row helpers ---------------------------------------------

    def pos(self, row_id: int, column_id: int) -> int:
        min_col = self.slice * SLICE_WIDTH
        if not (min_col <= column_id < min_col + SLICE_WIDTH):
            raise ValueError("column out of bounds")
        return row_id * SLICE_WIDTH + (column_id % SLICE_WIDTH)

    def row(self, row_id: int, check_cache: bool = True,
            update_cache: bool = True) -> Bitmap:
        """Materialize a row as a one-segment result Bitmap of absolute
        column ids (reference fragment.go:338-367)."""
        with self._mu:
            self._verify_on_read()
            if self.tier is not None:
                self._tier_gate(row_id=row_id)
            if check_cache:
                cached = self.row_cache.fetch(row_id)
                if cached is not None:
                    return cached
            data = self.storage.offset_range(self.slice * SLICE_WIDTH,
                                             row_id * SLICE_WIDTH,
                                             (row_id + 1) * SLICE_WIDTH)
            bm = Bitmap()
            bm.add_segment(data, self.slice, writable=False)
            if update_cache:
                self.row_cache.add(row_id, bm)
            return bm

    def pack_row(self, row_id: int, out: np.ndarray,
                 cached: bool = True) -> np.ndarray:
        """Copy one row's packed slice-local words into ``out``.

        ``out`` is a caller-provided zeroed u32[WORDS_PER_SLICE] buffer —
        the executor's mesh fast path fills one [leaf, slice] plane of
        its batched block per call. With ``cached`` (the default for hot
        leaf rows) packed words come from the residency manager's host
        cache, so repeated queries memcpy instead of re-walking roaring
        containers; bulk packs of sets larger than the cache budget pass
        ``cached=False`` to avoid churning the LRU for a 0% hit rate."""
        from ..ops.packed import pack_storage_row
        with self._mu:
            self._verify_on_read()
            if self.tier is not None:
                self._tier_gate(row_id=row_id)
            if cached:
                out[:] = self.device.host_row_words(self.storage, row_id)
            else:
                pack_storage_row(self.storage, row_id, out)
        return out

    def row_count(self, row_id: int) -> int:
        return self.storage.count_range(row_id * SLICE_WIDTH,
                                        (row_id + 1) * SLICE_WIDTH)

    def max_row_id(self) -> int:
        return self.storage.max() // SLICE_WIDTH

    # -- mutation ------------------------------------------------------------

    def set_bit(self, row_id: int, column_id: int) -> bool:
        with self._mu:
            return self._mutate(row_id, column_id, set=True)

    def clear_bit(self, row_id: int, column_id: int) -> bool:
        with self._mu:
            return self._mutate(row_id, column_id, set=False)

    def _mutate(self, row_id: int, column_id: int, set: bool) -> bool:
        # The per-op serving hot path (ISSUE 8): bounds + position
        # arithmetic inlined (pos() was a measured frame at per-op
        # rates; column_id - min_col == column_id % SLICE_WIDTH once
        # bounds-checked), every post-mutate maintenance step on
        # pre-bound locals.
        min_col = self.slice * SLICE_WIDTH
        if not (min_col <= column_id < min_col + SLICE_WIDTH):
            raise ValueError("column out of bounds")
        if self.tier is not None and self.tier_state != "hot":
            # Writes promote fully: the rank cache must cover every
            # row before cache.add maintains it incrementally.
            self._tier_gate(full=True, write=True)
        pos = row_id * SLICE_WIDTH + (column_id - min_col)
        storage = self.storage
        changed = storage.add(pos) if set else storage.remove(pos)
        if not changed:
            return False
        _accounting.note_bits_written(1)
        self._epoch += 1
        self.checksums.pop(row_id // HASH_BLOCK_SIZE, None)
        self.row_cache.invalidate(row_id)
        self.device.invalidate_row(row_id)
        row_counts = self._row_counts
        cur = row_counts.get(row_id)
        if cur is None:
            count = self.row_count(row_id)  # already post-mutation
        else:
            count = cur + (1 if set else -1)
        if len(row_counts) >= _ROW_COUNT_CAP:
            row_counts.clear()
        row_counts[row_id] = count
        self.cache.add(row_id, count)
        if self.stats is not None:
            self.stats.count("setN" if set else "clearN", 1)
        if storage.op_n > MAX_OP_N and not self._snap_mu.locked():
            self.snapshot(sync=False)
        return True

    def set_bits(self, row_ids, column_ids) -> np.ndarray:
        """Batched SetBit: one native crossing for container mutation +
        WAL construction, one group-commit op-log append, batched cache
        maintenance. Durability is identical to per-op set_bit — every
        changed bit has a checksummed WAL record on disk before this
        returns (a crash tears at worst the batch's final partial
        record, which the torn-tail trim on open already handles).
        Returns the sorted changed positions (row*SLICE_WIDTH +
        slice-local col) so callers can map per-op results; its length
        is the newly-set-bit count. The per-op ``set_bit`` stays as the
        single-op fallback (fragment.go:369-459)."""
        return self._mutate_batch(row_ids, column_ids, set=True)

    def clear_bits(self, row_ids, column_ids) -> np.ndarray:
        """Batched ClearBit (see set_bits)."""
        return self._mutate_batch(row_ids, column_ids, set=False)

    def _mutate_batch(self, row_ids, column_ids, set: bool) -> np.ndarray:
        rows = np.asarray(row_ids, dtype=np.uint64)
        cols = np.asarray(column_ids, dtype=np.uint64)
        if len(rows) != len(cols):
            raise ValueError("row/column id length mismatch")
        if not len(rows):
            return np.empty(0, dtype=np.uint64)
        min_col = self.slice * SLICE_WIDTH
        if (int(cols.min()) < min_col
                or int(cols.max()) >= min_col + SLICE_WIDTH):
            raise ValueError("column out of bounds")
        positions = rows * np.uint64(SLICE_WIDTH) + (
            cols % np.uint64(SLICE_WIDTH))
        return self._mutate_batch_positions(positions, set)

    def _mutate_batch_positions(self, positions: np.ndarray,
                                set: bool) -> np.ndarray:
        row_shift = np.uint64(SLICE_WIDTH.bit_length() - 1)
        with self._mu:
            if self.tier is not None and self.tier_state != "hot":
                self._tier_gate(full=True, write=True)
            changed = self.storage.apply_batch(positions, set=set,
                                               wal=True)
            if not len(changed):
                return changed
            _accounting.note_bits_written(len(changed))
            self._epoch += 1
            ch_rows, deltas = np.unique(changed >> row_shift,
                                        return_counts=True)
            row_counts = self._row_counts
            if len(row_counts) + len(ch_rows) >= _ROW_COUNT_CAP:
                row_counts.clear()
            sign = 1 if set else -1
            cache_add = self.cache.bulk_add
            for rid, d in zip(ch_rows.tolist(), deltas.tolist()):
                self.checksums.pop(rid // HASH_BLOCK_SIZE, None)
                self.row_cache.invalidate(rid)
                cur = row_counts.get(rid)
                if cur is None:
                    count = self.row_count(rid)  # already post-mutation
                else:
                    count = cur + sign * d
                row_counts[rid] = count
                cache_add(rid, count)
            self.cache.invalidate()
            self.device.invalidate_rows(ch_rows.tolist())
            if self.stats is not None:
                self.stats.count("setN" if set else "clearN",
                                 len(changed))
            self._increment_op_n()
            return changed

    def _increment_op_n(self) -> None:
        # locked() is a racy peek, but benign in both directions: a
        # stale False just try-acquires (and fails fast) inside
        # _snapshot_async; a stale True means the NEXT op re-triggers.
        # Walking the full snapshot() chain per op while a background
        # worker lagged behind the write rate was a measured chunk of
        # per-op latency.
        if self.storage.op_n > MAX_OP_N and not self._snap_mu.locked():
            self.snapshot(sync=False)

    def wal_barrier(self) -> None:
        """Commit barrier: every mutation applied so far has its WAL
        record in the OS (fsynced per PILOSA_TPU_WAL_FSYNC) when this
        returns. The serving layer calls the process-wide
        ``storage.wal.barrier_all()`` before acking write requests;
        library callers mutating fragments directly use this (or
        ``close()``) to get the same durability point."""
        wal = self._wal
        if wal is not None:
            wal.barrier()

    def snapshot(self, sync: bool = True,
                 reason: str = "storage") -> None:
        """Atomically rewrite the data file from current state
        (reference fragment.go:991-1057).

        ``sync=False`` (the per-op MAX_OP_N trigger) serializes a
        COW-frozen capture on a BACKGROUND thread and splices the ops
        appended meanwhile from the old file's WAL tail at swap time —
        the write path stops paying the ~15-30 ms serialization every
        2000 ops (it was a third of per-op latency), while durability
        is unchanged: every op is already in the old file's WAL, so a
        crash at ANY point replays identically. Bulk paths that detach
        the op writer (import, merge apply, restore) MUST use
        sync=True — their mutations exist nowhere but memory until the
        snapshot lands.

        Fast path: the rewritten file is swapped under the live storage
        object — no close/re-unmarshal/remap, which cost ~100 ms per
        MAX_OP_N=2000 ops (most of the steady-state write path). The
        in-memory containers are already the state just serialized, so
        only the fd, the flock, and the op counter change. Every
        ``_REMAP_EVERY``-th snapshot takes the full reopen instead: it
        re-establishes zero-copy mapped containers, un-pinning old map
        generations that copy-on-write views would otherwise keep alive
        indefinitely."""
        if not sync:
            self._snapshot_async()
            return
        # Lock order: _snap_mu (waits out / blocks any worker) then
        # _mu. Callers MUST NOT hold _mu here — import/merge release
        # it before snapshotting (the worker needs _mu to finish, so
        # joining under _mu would deadlock).
        with self._snap_mu:
            self._snapshot_locked(reason=reason)

    def _snapshot_locked(self, reason: str = "storage") -> None:
        with self._mu:
            with self.logger.track("fragment: snapshot %s/%s/%s/%d",
                                   self.index, self.frame, self.view,
                                   self.slice):
                # No unmap/copy-out: write_to reads the mapped
                # containers directly, and _close_storage just drops
                # the map reference (see its comment).
                t0 = time.perf_counter()
                tmp = self.path + ".snapshotting"
                try:
                    with open(tmp, "wb") as f:
                        self.storage.write_to(f, footer=True)
                        # The hit sits AFTER the body write so corrupt
                        # mode can flip real bits in the just-written
                        # snapshot (error/torn/enospc semantics are
                        # unchanged: the tmp file is discarded either
                        # way and the old file stays the record).
                        if _fp.ACTIVE is not None:
                            _fp.ACTIVE.hit("snapshot.write", writer=f)
                        f.flush()
                        os.fsync(f.fileno())
                except OSError as e:
                    # A full disk flips the node write-unready
                    # (fault.diskfull → writes answer 507, reads keep
                    # serving) before the failure propagates; the old
                    # snapshot+WAL stays the file of record.
                    from ..fault import diskfull as _diskfull
                    _diskfull.note_if_enospc(e, "snapshot.write",
                                             self.path)
                    raise
                self._swap_data_file(tmp, new_op_n=0)
                snap_s = time.perf_counter() - t0
                # The snapshot leg of the import-stage breakdown
                # (decode/apply land in the wire-import handler) —
                # only for snapshots the IMPORT path forced; op-log
                # threshold and anti-entropy rewrites would pollute
                # the import attribution.
                if reason == "import":
                    obs_metrics.IMPORT_STAGE_SECONDS.labels(
                        "snapshot").observe(snap_s)
                if self.stats is not None:
                    # Distribution, not last-write-wins: the expvar
                    # client aggregates count/sum/min/max and the
                    # registry bridge buckets it (obs.metrics).
                    self.stats.timing(
                        "snapshotDurationNs", snap_s * 1e9)

    def _swap_data_file(self, tmp: str, new_op_n: int) -> None:
        """Swap ``tmp`` in as the data file (caller holds _mu; one
        shared implementation for the sync and background paths).
        Fast path: replace the path, flock + attach a new fd — flock
        is per-inode, so the old fd's lock cannot conflict, and the
        old map stays alive while mapped container views pin it. Every
        ``_REMAP_EVERY``-th snapshot does the full close/reopen instead
        (re-establishes zero-copy mapped containers). A failed swap
        falls back to the full reopen so the WAL is never silently
        left detached; if THAT also fails the exception propagates and
        the fragment is visibly broken rather than quietly
        unlogged."""
        self._snapshot_n += 1
        # The view token moves with every storage swap: a route
        # resolved before the swap is resolved again after it. The bits
        # are unchanged, so the fragment's own generation stays and no
        # resident slab is retired (a swap that lands corrupt replaces
        # the bits and retires them: _open_storage_quarantining).
        self.device.bump_owner()
        if self._snapshot_n % _REMAP_EVERY == 0:
            self._close_storage()
            os.replace(tmp, self.path)
            # Quarantining reopen: a snapshot that landed corrupt
            # (failpoint corrupt mode, real bit rot in the write path)
            # must degrade to quarantine + repair, not brick the
            # fragment mid-swap.
            self._open_storage_quarantining()
            return
        self.storage.op_writer = None
        os.replace(tmp, self.path)
        try:
            new_file = open(self.path, "a+b", buffering=0)
            fcntl.flock(new_file.fileno(),
                        fcntl.LOCK_EX | fcntl.LOCK_NB)
        except BaseException:
            self._close_storage()
            self._open_storage_quarantining()
            return
        old_file, self._file = self._file, new_file
        self._mmap = None
        if old_file is not None:
            try:
                fcntl.flock(old_file.fileno(), fcntl.LOCK_UN)
            except OSError:
                pass
            old_file.close()
        new_file.seek(0, os.SEEK_END)
        self.storage.op_n = new_op_n
        if self._wal is not None:
            # The snapshot body covers every applied mutation, so any
            # pending (even failed/torn) records are superseded; the
            # WAL continues over the fresh file with a clean slate.
            self._wal.reset_file(new_file, clear_pending=True)
            self.storage.op_writer = self._wal
        else:
            self.storage.op_writer = new_file

    def _join_snapshot(self) -> None:
        """Barrier: returns once no background snapshot is in flight
        (the worker holds _snap_mu for its entire run)."""
        with self._snap_mu:
            pass

    def _snapshot_async(self) -> None:
        # Called UNDER _mu (the per-op MAX_OP_N trigger): may only
        # TRY-acquire _snap_mu — blocking here would invert the
        # _snap_mu → _mu lock order against sync snapshot/close.
        if not self._snap_mu.acquire(blocking=False):
            return  # a worker or sync snapshot is running; op_n
            # keeps re-triggering until one lands
        try:
            if self._wal is not None:
                # The splice contract below needs the FILE to hold
                # every op appended so far: tail_off divides "covered
                # by the frozen body" from "spliced from the WAL tail",
                # so pending userspace records must land first. (A
                # failed/torn log raises here — fail-stop: the file
                # past the flushed prefix is not trustworthy, and a
                # reopen trims to exactly that prefix.)
                self._wal.flush(None, sync=False)
            frozen = self.storage.freeze()
            tail_off = self._file.seek(0, os.SEEK_END)
        except BaseException:
            self._snap_mu.release()
            raise
        # _snap_mu intentionally stays held; the worker releases it
        # (threading.Lock allows cross-thread release).
        threading.Thread(
            target=self._snapshot_worker, args=(frozen, tail_off),
            name="frag-snapshot", daemon=True).start()

    def _snapshot_worker(self, frozen, tail_off: int) -> None:
        with sched_context.background_tick("snapshot"):
            self._snapshot_write(frozen, tail_off)

    def _snapshot_write(self, frozen, tail_off: int) -> None:
        # Runs with _snap_mu held (acquired by _snapshot_async,
        # released here — a plain Lock supports cross-thread release).
        try:
            with self.logger.track(
                    "fragment: async snapshot %s/%s/%s/%d", self.index,
                    self.frame, self.view, self.slice):
                tmp = self.path + ".snapshotting"
                try:
                    with open(tmp, "wb") as f:
                        # The expensive serialize + fsync of the frozen
                        # body runs with NO fragment lock held; writers
                        # keep appending to the old file's WAL.
                        roaring.write_frozen(frozen, f, footer=True)
                        # Crash-mid-snapshot injection: a fault here
                        # leaves a partial tmp file that is never
                        # swapped in — the old snapshot+WAL stays the
                        # file of record and the next MAX_OP_N trigger
                        # retries (the OSError handler below). Corrupt
                        # mode instead flips real bits in the written
                        # body, which the open-time / scrub checks
                        # must catch downstream.
                        if _fp.ACTIVE is not None:
                            _fp.ACTIVE.hit("snapshot.write", writer=f)
                        f.flush()
                        os.fsync(f.fileno())
                        with self._mu:
                            # Splice the ops that landed since the
                            # freeze, then swap — brief: the body is
                            # already on disk, only the tail pages
                            # need syncing.
                            if self._wal is not None:
                                # Writers appended under _mu; get their
                                # records into the old file so the tail
                                # read below sees them. WalError (torn
                                # log) aborts the swap via the OSError
                                # handler — the old file stays the file
                                # of record.
                                self._wal.flush(None, sync=False)
                            with open(self.path, "rb") as rf:
                                rf.seek(tail_off)
                                tail = rf.read()
                            f.write(tail)
                            f.flush()
                            os.fsync(f.fileno())
                            self._swap_data_file(
                                tmp,
                                new_op_n=len(tail) // roaring.OP_SIZE)
                except OSError as e:
                    # Pre-swap serialization IO failure: op_writer was
                    # never detached, the old snapshot+WAL remains the
                    # file of record, and the next MAX_OP_N trigger
                    # retries. (_swap_data_file failures are NOT
                    # caught: its own fallback reopen either restores
                    # a consistent state or propagates, leaving the
                    # fragment visibly broken — never quietly
                    # unlogged.) ENOSPC additionally flips the node
                    # write-unready (fault.diskfull) so the retry
                    # pressure stops at the HTTP layer with 507s.
                    from ..fault import diskfull as _diskfull
                    _diskfull.note_if_enospc(e, "snapshot.write",
                                             self.path)
                    self.logger.printf(
                        "fragment: async snapshot failed for"
                        " %s/%s/%s/%d: %s", self.index, self.frame,
                        self.view, self.slice, e)
        finally:
            self._snap_mu.release()

    def import_bits(self, row_ids, column_ids) -> None:
        """Bulk import: direct adds with the op-log detached, then snapshot
        (reference fragment.go:924-989)."""
        rows = np.asarray(row_ids, dtype=np.uint64)
        cols = np.asarray(column_ids, dtype=np.uint64)
        if len(rows) != len(cols):
            raise ValueError("row/column id length mismatch")
        min_col = self.slice * SLICE_WIDTH
        if len(cols) and (int(cols.min()) < min_col
                          or int(cols.max()) >= min_col + SLICE_WIDTH):
            raise ValueError("column out of bounds")
        positions = rows * np.uint64(SLICE_WIDTH) + (
            cols % np.uint64(SLICE_WIDTH))
        self.import_positions(positions)

    def clear_positions(self, positions: np.ndarray) -> np.ndarray:
        """Batched clear of slice-local bit positions through the WAL'd
        batch engine (the BSI value-import lane clears stale planes of
        re-imported columns with it). Returns the changed positions."""
        return self._mutate_batch_positions(
            np.asarray(positions, dtype=np.uint64), set=False)

    def import_positions(self, positions: np.ndarray) -> None:
        """Bulk import of slice-local bit positions (row*SLICE_WIDTH +
        col%SLICE_WIDTH) — the frame-level packed-sort import lane
        feeds each fragment its span of ONE globally sorted position
        vector, so no per-fragment re-sort happens here (add_many's
        is-sorted check passes on that lane)."""
        positions = np.asarray(positions, dtype=np.uint64)
        # Gate read under _mu: op_writer is swapped by snapshot/restore
        # code, and although every such path restores it under the same
        # _mu hold today, that invariant is one refactor away from
        # breaking silently (ADVICE r5 #3) — the lock is noise next to
        # the import itself.
        with self._mu:
            if self.tier is not None and self.tier_state != "hot":
                self._tier_gate(full=True, write=True)
            small = (len(positions) * 16 < len(self.storage.keys)
                     and self.storage.op_writer is not None)
        if small:
            # Small import into a large fragment: the WAL'd batch engine
            # is strictly cheaper than the detach-then-full-snapshot
            # import contract (a 3-bit /import into a 400 K-container
            # fragment paid ~0.9 s of snapshot serialization), and
            # strictly MORE durable — the bits are group-commit WAL'd
            # before return instead of living only in memory until the
            # snapshot lands.
            self._mutate_batch_positions(positions, set=True)
            self.wal_barrier()
            return
        # WAL-first bulk import: append one blob of add records for the
        # whole block (vectorized build, idempotent on replay — re-adds
        # of already-set bits are no-ops, exactly like op replay), bulk
        # apply, then a commit barrier. The sync snapshot the vintage
        # import contract paid per request (serialize whole fragment +
        # fsync, ~100 ms/slice — THE wire-import bound)
        # moves to the MAX_OP_N async cadence; reopen replays the
        # records through the vectorized op-log lane instead. Imports
        # too large to sensibly hold as op records keep the vintage
        # detach-then-snapshot contract.
        wal_first = (self.storage.op_writer is not None
                     and len(positions) * roaring.OP_SIZE
                     <= _WAL_IMPORT_MAX_BYTES)
        with self._mu:
            self._epoch += 1
            _accounting.note_bits_written(len(positions))
            if wal_first:
                roaring._wal_write(self.storage.op_writer,
                                   roaring._wal_blob(positions,
                                                     roaring.OP_ADD))
                # MAX_OP_N bounds REOPEN REPLAY time, and a blob's
                # add-run replays through the vectorized bulk lane at
                # ~16x the discrete-op rate (roaring._replay_ops) —
                # so a blob bit carries 1/16th the snapshot pressure
                # of a discrete op. Unweighted, every import block
                # larger than MAX_OP_N forced a full snapshot whose
                # GIL-held serialization convoyed with the NEXT
                # block's apply (the measured wire-import long pole).
                self.storage.op_n += max(
                    1, len(positions) // _BLOB_OP_WEIGHT)
                self.storage.add_many(positions)
            else:
                writer, self.storage.op_writer = \
                    self.storage.op_writer, None
                try:
                    self.storage.add_many(positions)
                finally:
                    self.storage.op_writer = writer
            if _RUN_OPTIMIZE:
                # Cardinality-adaptive representation pass (roaring run
                # containers): bulk imports are where run-heavy data
                # (timestamp views, BSI planes) lands, so this is the
                # one site that (re)introduces run containers; the
                # snapshot below persists them via the runs cookie.
                # Restricted to containers this block put an ADJACENT
                # value pair into: a run form needs adjacency to beat
                # the legacy kinds, and run-shaped data carries its
                # adjacency in the import block itself — so a random
                # sparse import (which can never win) skips the pass
                # entirely instead of re-pricing every touched
                # container (measured: the unrestricted pass was 40%
                # of a 1M-bit import).
                srt = (positions if len(positions) < 2
                       or positions[0] <= positions[-1] else None)
                srt = np.sort(positions) if srt is None else srt
                adj = np.flatnonzero(np.diff(srt) == np.uint64(1))
                if len(adj):
                    self.storage.optimize(
                        sort_dedupe(srt[adj] >> np.uint64(16)))
            # Post-import row counts in ONE pass over the container
            # table: positions are row*SLICE_WIDTH + col, so a
            # container's row is its key >> log2(SLICE_WIDTH/65536) and
            # a row's count is the sum of its containers' cardinalities
            # (slice rows align exactly on container boundaries). The
            # per-row count_range walk this replaces was the bulk-import
            # long pole at 10^5 distinct rows (~230 us/row).
            shift = np.uint64((SLICE_WIDTH // 65536).bit_length() - 1)
            key_arr = self.storage._keys_np()
            # Packed-lane positions arrive sorted: sort_dedupe's linear
            # pass replaces np.unique's re-sort.
            uniq_rows = sort_dedupe(positions // np.uint64(SLICE_WIDTH))
            conts = self.storage.containers
            if len(uniq_rows) * 32 < len(key_arr):
                # Small import into a large fragment: sum only each
                # touched row's <=16-container key span instead of
                # walking the whole container table (review finding:
                # the full pass made every tiny /import request pay
                # O(all containers)).
                lo = np.searchsorted(key_arr, uniq_rows << shift)
                hi = np.searchsorted(key_arr,
                                     (uniq_rows + np.uint64(1)) << shift)
                cnts = np.fromiter(
                    (sum(conts[i].n for i in range(l, h))
                     for l, h in zip(lo.tolist(), hi.tolist())),
                    np.int64, len(uniq_rows))
            else:
                cards = np.fromiter((c.n for c in conts), np.int64,
                                    len(key_arr))
                crows = key_arr >> shift
                gb = np.flatnonzero(crows[1:] != crows[:-1]) + 1
                gstarts = np.concatenate(([0], gb)) if len(crows) else gb
                present_rows = crows[gstarts] if len(crows) else crows
                row_sums = (np.add.reduceat(cards, gstarts)
                            if len(crows) else cards)
                pos = np.searchsorted(present_rows, uniq_rows)
                cnts = row_sums[np.minimum(pos, max(len(row_sums) - 1,
                                                    0))]
                cnts[(pos >= len(present_rows))
                     | (present_rows[np.minimum(
                         pos, max(len(present_rows) - 1, 0))]
                        != uniq_rows)] = 0
            under_cap = len(self._row_counts) < _ROW_COUNT_CAP
            for rid, cnt in zip(uniq_rows.tolist(), cnts.tolist()):
                if rid in self._row_counts or under_cap:
                    self._row_counts[rid] = cnt
                    under_cap = len(self._row_counts) < _ROW_COUNT_CAP
                self.cache.bulk_add(rid, cnt)
            self.cache.recalculate()
            self.row_cache.clear()
            self.device.invalidate_all()
            self.checksums.clear()
        # Outside _mu: the sync snapshot takes _snap_mu then _mu (the
        # worker needs _mu to finish, so snapshotting under _mu would
        # deadlock the join).
        if wal_first:
            # The records are appended; commit them (one group flush,
            # coalesced with any concurrent import's barrier) and let
            # the op-count trigger schedule the snapshot in the
            # background — the import request path no longer pays it.
            with self._mu:
                self._increment_op_n()
            self.wal_barrier()
        else:
            # Vintage contract: the bulk adds were never WAL'd, so the
            # mutations exist nowhere but memory until this lands.
            self.snapshot(reason="import")

    # -- TopN ----------------------------------------------------------------

    def _top_pairs(self, row_ids: list[int]) -> list[Pair]:
        # reference fragment.go:627-677
        if not row_ids:
            self.cache.invalidate()
            return self.cache.top()
        pairs = []
        for rid in row_ids:
            n = self.cache.get(rid)
            if n <= 0:
                n = self.row_count(rid)
            if n > 0:
                pairs.append(Pair(rid, n))
        return pairs

    _EMPTY_COUNTS = (np.empty(0, dtype=np.int64),
                     np.empty(0, dtype=np.int64))

    def row_containers(self, row_id: int) -> roaring.Bitmap:
        """One row's containers, keyed 0..15: what a residency fill
        packs (ops.packed.pack_slab). Only references are taken under
        the fragment lock, the copy-on-write shared views ``row()``
        hands out too: every mutation replaces an array or run buffer
        and copies a bitmap's words before writing them once the
        container is marked ``mapped`` (roaring ``_guard_inplace`` /
        ``_unmap``, native/fastmutate.c bails on the flag), and a
        swapped-out map lives as long as a view pins it — so the
        packing itself runs outside the lock on what the row held at
        this moment."""
        with self._mu:
            self._verify_on_read()
            if self.tier is not None:
                self._tier_gate(row_id=row_id)
            return self.storage.offset_range(0, row_id * SLICE_WIDTH,
                                             (row_id + 1) * SLICE_WIDTH)

    def positions(self) -> np.ndarray:
        """Every set position (row * SLICE_WIDTH + column) as one sorted
        u64 vector, read under the lock with ``top``'s read checks: what
        a whole-frame device form packs
        (parallel.residency.frame_positions)."""
        with self._mu:
            self._verify_on_read()
            if self.tier is not None:
                self._tier_gate(full=True)
            return self.storage.all_positions()

    def _cached_total_bits(self) -> int:
        """storage.count() walks every container in Python (~115 ms
        across 256 c5 fragments); cache per mutation epoch."""
        hit = getattr(self, "_total_bits", None)
        if hit is not None and hit[0] == self._epoch:
            return hit[1]
        n = self.storage.count()
        self._total_bits = (self._epoch, n)
        return n

    def container_stats(self) -> dict:
        """Per-kind container counts/resident bytes/run intervals
        (roaring.Bitmap.container_stats), cached per mutation epoch —
        the runtime collector samples every open fragment on its
        cadence, and the underlying walk is O(containers)."""
        hit = getattr(self, "_container_stats", None)
        if hit is not None and hit[0] == self._epoch:
            return hit[1]
        with self._mu:
            stats = self.storage.container_stats()
        self._container_stats = (self._epoch, stats)
        return stats

    def _cached_positions(self) -> np.ndarray:
        """all_positions per mutation epoch: every src's first count
        map (and any other whole-fragment pass) shares one walk. Only
        cached up to _POSITIONS_CACHE_BITS (32 MB resident); bigger
        fragments rebuild per pass rather than pinning hundreds of MB
        across a read-mostly fleet of fragments."""
        hit = getattr(self, "_positions", None)
        if hit is not None and hit[0] == self._epoch:
            return hit[1]
        pos = self.storage.all_positions()
        if len(pos) <= _POSITIONS_CACHE_BITS:
            self._positions = (self._epoch, pos)
        else:
            self._positions = None
        return pos

    def present_rows(self):
        """Sorted row ids holding >=1 bit, cached per mutation epoch —
        lets the TopN ids-refetch skip row_count for candidates with no
        bits here (at 1024 slices x 1000 candidates that recount was
        ~900 K walks per query). None when the fragment is too big to
        dump positions cheaply; callers then recount per id."""
        hit = getattr(self, "_present_rows", None)
        if hit is not None and hit[0] == self._epoch:
            return hit[1]
        if self._cached_total_bits() > _SRC_VECTOR_BITS:
            return None
        rows = np.unique(self._cached_positions()
                         >> np.uint64(SLICE_WIDTH.bit_length() - 1))
        self._present_rows = (self._epoch, rows)
        return rows

    def _host_src_count_map(self, src: Bitmap
                            ) -> tuple[np.ndarray, np.ndarray]:
        """src ∩ row intersection counts for EVERY row of this fragment
        in one vectorized pass, as (sorted row ids, counts).

        O(fragment bits) once, instead of one roaring walk per visited
        candidate — the unbounded rank-cache src-TopN walk (up to 50 K
        rows) costs seconds through per-row Python calls and ~10 ms
        here (reference does per-row counts, fragment.go:529-560, but
        its per-call cost is nanoseconds; ours is not)."""
        key = self._src_key(src)
        if key is None:
            return self._EMPTY_COUNTS
        hit = self._src_counts.get(key)
        if hit is not None and hit[0] == self._epoch:
            return hit[1]
        # Cache miss: NOW materialize the slice-local columns (the key
        # memo deliberately does not retain them — pinning the
        # uncompressed u64 vector per cached row object would dwarf
        # the roaring data it came from).
        seg = src._segment(self.slice, False)
        src_cols = seg.data.values() % np.uint64(SLICE_WIDTH)
        return self._compute_src_count_map(src_cols,
                                           np.uint64(SLICE_WIDTH), key)

    def _src_key(self, src: Bitmap):
        """sha1 key of the slice-local src columns for the src-count
        cache (None = absent/empty segment), memoized on the segment's
        roaring data: row() hands out the SAME cached Bitmap object
        across repeat queries (row_cache), and result bitmaps are COW
        — so the values walk + sha1 runs once per materialized object
        instead of twice per slice per query (both TopN phases key the
        same map). Guarded by Bitmap.version against in-place
        mutation; only the 20-byte digest is retained."""
        seg = src._segment(self.slice, False)
        if seg is None:
            return None
        data = seg.data
        memo = getattr(data, "_src_key_memo", None)
        if memo is not None and memo[0] == data.version:
            return memo[1]
        src_cols = data.values() % np.uint64(SLICE_WIDTH)
        key = (hashlib.sha1(src_cols.tobytes()).digest()
               if len(src_cols) else None)
        data._src_key_memo = (data.version, key)
        return key

    def _host_src_count_map_cached(self, src: Bitmap):
        """The cached (ids, counts) map for this src if one is already
        current — NO compute. TopN's exact phase (few re-queried
        candidates per slice) probes this: the candidate phase of the
        same query built the map moments earlier, so the per-candidate
        roaring intersections it would otherwise do are free gathers."""
        key = self._src_key(src)
        if key is None:
            return self._EMPTY_COUNTS
        hit = self._src_counts.get(key)
        if hit is not None and hit[0] == self._epoch:
            return hit[1]
        return None

    def _compute_src_count_map(self, src_cols, w, key
                               ) -> tuple[np.ndarray, np.ndarray]:
        total_bits = self._cached_total_bits()
        if total_bits <= _SRC_VECTOR_BITS:
            # One fully vectorized pass: the per-container chunked walk
            # below costs ~4 us of Python per container, which IS the
            # first-query latency on ultra-sparse fragments (c5: 1.7 K
            # near-empty containers per fragment x 256 fragments).
            positions = self._cached_positions()
            hits = positions[np.isin(positions % w, src_cols)]
            if len(hits):
                out = np.unique((hits // w).astype(np.int64),
                                return_counts=True)
            else:
                z = np.empty(0, dtype=np.int64)
                out = (z, z)
            self._src_counts[key] = (self._epoch, out)
            while len(self._src_counts) > 4:
                self._src_counts.pop(next(iter(self._src_counts)))
            return out
        # Partial (ids, counts) maps, folded every ~1 M matched
        # positions: peak memory is bounded by DISTINCT row ids, not by
        # matched bits (a broad src over 100 M matched bits would
        # otherwise hold ~800 MB of int64 row ids before one unique).
        partial_ids: list[np.ndarray] = []
        partial_counts: list[np.ndarray] = []
        hit_rows: list[np.ndarray] = []
        hit_len = 0
        # Batch container chunks to ~1 M positions per isin: sparse
        # fragments have millions of near-empty containers, and a
        # per-container isin pays its sort setup millions of times.
        batch: list[np.ndarray] = []
        batch_len = 0

        def fold_hits() -> None:
            nonlocal hit_rows, hit_len
            if not hit_rows:
                return
            rows = (hit_rows[0] if len(hit_rows) == 1
                    else np.concatenate(hit_rows))
            hit_rows, hit_len = [], 0
            ids, counts = np.unique(rows, return_counts=True)
            partial_ids.append(ids)
            partial_counts.append(counts)

        def flush() -> None:
            nonlocal batch, batch_len, hit_len
            if not batch:
                return
            vals = batch[0] if len(batch) == 1 else np.concatenate(batch)
            batch, batch_len = [], 0
            hits = vals[np.isin(vals % w, src_cols)]
            if len(hits):
                hit_rows.append((hits // w).astype(np.int64))
                hit_len += len(hits)
                if hit_len >= _SRC_FOLD_POSITIONS:
                    fold_hits()

        for vals in self.storage.value_chunks():
            batch.append(vals)
            batch_len += len(vals)
            if batch_len >= (1 << 20):
                flush()
        flush()
        fold_hits()
        if partial_ids:
            # Merge the bounded partials: (sorted row ids, counts) — NOT
            # a bincount array, whose size is max-row-id+1 and explodes
            # on sparse huge ids.
            if len(partial_ids) == 1:
                out = (partial_ids[0], partial_counts[0])
            else:
                all_ids = np.concatenate(partial_ids)
                all_counts = np.concatenate(partial_counts)
                ids, inv = np.unique(all_ids, return_inverse=True)
                out = (ids, np.bincount(inv, weights=all_counts)
                       .astype(np.int64))
        else:
            z = np.empty(0, dtype=np.int64)
            out = (z, z)
        self._src_counts[key] = (self._epoch, out)
        while len(self._src_counts) > 4:
            self._src_counts.pop(next(iter(self._src_counts)))
        return out

    def fold_scan_pays(self, row_ids) -> bool:
        """Should a fold over these rows take fold_rows over per-row
        roaring reads? Since fold_rows switched from a whole-fragment
        scan to gathering only the target rows' container key spans,
        its cost is O(selected bits) — the same data the per-row path
        reads, minus a Bitmap wrapper and merge per row — so at the
        many-leaf shapes that reach this gate it always pays. (The old
        heuristic modeled the retired whole-fragment walk AND paid an
        O(all containers) count() per decision to do it.)"""
        return True

    def fold_rows(self, op: str, row_ids: list[int]) -> np.ndarray:
        """Slice-local columns of a left-fold of ``op`` over the given
        rows, gathered from the rows' container key spans in one
        vectorized pass instead of one roaring merge per row (the
        reference folds per row, executor.go:253-268; at 1000-row
        fan-outs that is the whole query cost on the host path).

        Semantics match the sequential fold: ``or`` = union of all;
        ``and`` = columns present in every distinct row; ``andnot`` =
        first row minus the union of the rest."""
        if op not in ("or", "and", "andnot"):
            raise ValueError(f"unknown fold op: {op!r}")
        if not row_ids:
            return np.empty(0, dtype=np.uint64)
        with self._mu:
            self._verify_on_read()
            if self.tier is not None:
                self._tier_gate(row_ids=row_ids)
            w = np.uint64(SLICE_WIDTH)
            ids = np.unique(np.asarray(row_ids, dtype=np.uint64))
            # Gather ONLY the target rows' container key spans (each
            # row covers exactly SLICE_WIDTH/65536 consecutive keys)
            # instead of walking the whole fragment through
            # value_chunks and masking with np.isin — at c2 scale
            # (1000 rows over a wide fragment) the whole-fragment walk
            # was most of the host fold's cost.
            shift = np.uint64((SLICE_WIDTH // 65536).bit_length() - 1)
            positions = self.storage.positions_for_key_ranges(
                ids << shift, (ids + np.uint64(1)) << shift)
            if not len(positions):
                return np.empty(0, dtype=np.uint64)
            rows = positions // w
            cols = positions % w
            if op == "or":
                return np.unique(cols)
            if op == "and":
                uniq, counts = np.unique(cols, return_counts=True)
                # (row, col) pairs are distinct, so a column's count is
                # the number of rows containing it.
                return uniq[counts == len(ids)]
            # andnot: row_ids[0] minus the union of the rest (the
            # sequential fold's left-to-right difference collapses to
            # exactly this). A repeat of the first row later in the
            # list subtracts it from itself — empty.
            first = np.uint64(row_ids[0])
            if any(np.uint64(r) == first for r in row_ids[1:]):
                return np.empty(0, dtype=np.uint64)
            first_cols = np.unique(cols[rows == first])
            rest_cols = np.unique(cols[rows != first])
            return first_cols[~np.isin(first_cols, rest_cols,
                                       assume_unique=True)]

    def top(self, opt: TopOptions = None) -> list[Pair]:
        """TopN with threshold pruning, attr filter, Tanimoto
        (reference fragment.go:490-625; same semantics, batched counts)."""
        opt = opt or TopOptions()
        with self._mu:
            self._verify_on_read()
            if self.tier is not None:
                # TopN ranks through the count cache, which demotion
                # flushed — a block-granular fault can't rebuild it,
                # so top() promotes fully (rank correctness over
                # laziness).
                self._tier_gate(full=True)
            # Array fast path for the plain TopN(frame, n) shape — no
            # source bitmap, no attribute filter, no tanimoto: the
            # answer is the first n rank-cache entries with count ≥
            # max(threshold, 1), which the heap replay below computes
            # identically but one Python object at a time. At config-3
            # scale (50 K-entry caches × 10 slices) this is the
            # candidate phase's entire cost.
            if (opt.src is None and not opt.row_ids
                    and not (opt.filter_field and opt.filter_values)
                    and opt.tanimoto_threshold <= 0
                    and hasattr(self.cache, "top_arrays")):
                self.cache.invalidate()
                ids, counts = self.cache.top_arrays()
                # counts are rank-sorted descending: the ≥-floor set is
                # a prefix, found by binary search on the reversed view
                # — no 50K-entry boolean mask per slice per query.
                floor = max(opt.min_threshold, 1)
                cut = len(counts) - int(np.searchsorted(
                    counts[::-1], floor, side="left"))
                ids, counts = ids[:cut], counts[:cut]
                if opt.n:
                    ids, counts = ids[:opt.n], counts[:opt.n]
                return [Pair(i, c) for i, c in zip(ids.tolist(),
                                                   counts.tolist())]
            # ids-form fast path (TopN's exact phase re-queries every
            # candidate on every slice): rank-sort the per-id counts,
            # skipping the heap replay — at 256 slices × ~200
            # candidates the replay's per-pair heap ops were phase 2's
            # whole cost. Identical output: the replay with row_ids has
            # n=0 (push all positives ≥ threshold) and pops in
            # (count desc, id asc) order, which is exactly pairs_sort.
            if (opt.src is None and opt.row_ids
                    and not (opt.filter_field and opt.filter_values)
                    and opt.tanimoto_threshold <= 0):
                floor = max(opt.min_threshold, 1)
                return cache_mod.pairs_sort(
                    p for p in self._top_pairs(opt.row_ids)
                    if p.count >= floor)
            # Candidate stream as numpy arrays when the rank cache can
            # serve them: the src path used to materialize a Pair per
            # cached row per slice (117 K objects per c5 query, ~60 ms
            # of its 112 ms repeat p50) just to feed the replay loop.
            if not opt.row_ids and hasattr(self.cache, "top_arrays"):
                self.cache.invalidate()
                cand_ids, cand_counts = self.cache.top_arrays()
                cand_ids = cand_ids.astype(np.int64)
                cand_counts = np.asarray(cand_counts)
            else:
                pairs = self._top_pairs(opt.row_ids)
                cand_ids = np.fromiter((p.id for p in pairs),
                                       dtype=np.int64, count=len(pairs))
                cand_counts = np.fromiter((p.count for p in pairs),
                                          dtype=np.int64,
                                          count=len(pairs))
            n = 0 if opt.row_ids else opt.n

            filters = None
            if opt.filter_field and opt.filter_values:
                filters = set(opt.filter_values)

            tanimoto = 0
            min_tan = max_tan = 0.0
            src_count = 0
            if opt.tanimoto_threshold > 0 and opt.src is not None:
                tanimoto = opt.tanimoto_threshold
                src_count = opt.src.count()
                min_tan = src_count * tanimoto / 100
                max_tan = src_count * 100 / tanimoto

            # Candidate ∩ src counts. Past a handful of candidates, ONE
            # vectorized pass over the fragment computes every row's
            # count (O(fragment bits), ~10 ms/slice, cached per src ×
            # mutation epoch), then zero-overlap candidates drop out
            # before the replay. Safe: a src-count-0 pair can never
            # push (the replay skips count==0) and removing it cannot
            # move the break point (the next visited pair's cache
            # count is ≤ the removed one's, so the break still fires
            # before any further push). Small candidate sets (point
            # lookups, short ids=[...]) keep the per-row roaring
            # intersection — a full-fragment scan for 3 rows is waste.
            # Per-slice device batching was measured strictly worse on
            # every shape — one sync per slice per query; cross-slice
            # batched exact counts with residency live on the
            # EXECUTOR's device path (_topn_exact_resident), where the
            # cost model routes them.
            count_ids = count_vals = None
            if opt.src is not None:
                if len(cand_ids) > self.SRC_MAP_MIN:
                    count_ids, count_vals = \
                        self._host_src_count_map(opt.src)
                else:
                    # Small candidate set (the exact phase's ids= form,
                    # point lookups): never WORTH computing the map,
                    # but if one is already cached — the candidate
                    # phase of this very query built it — gathers beat
                    # per-candidate roaring intersections.
                    cached = self._host_src_count_map_cached(opt.src)
                    if cached is not None:
                        count_ids, count_vals = cached
            if count_ids is not None:
                scnt = None
                if len(cand_ids):
                    # count_ids is sorted: membership via searchsorted
                    # beats np.isin's hash/sort machinery at rank-cache
                    # scale (up to 50 K candidates x 256 slices/query);
                    # the same probe's indices serve as the src-count
                    # gather, so the vectorized replay never re-probes.
                    keep, at = arrays_mod.searchsorted_membership(
                        count_ids, cand_ids)
                    cand_ids = cand_ids[keep]
                    cand_counts = cand_counts[keep]
                    scnt = count_vals[at[keep]]
                if (filters is None and tanimoto == 0 and n > 0
                        and len(cand_ids)):
                    return self._top_src_vectorized(
                        cand_ids, cand_counts, scnt, n,
                        opt.min_threshold)

            def src_count_of(rid: int) -> int:
                if count_ids is None:
                    return opt.src.intersection_count(self.row(rid))
                i = np.searchsorted(count_ids, rid)
                if i < len(count_ids) and count_ids[i] == rid:
                    return int(count_vals[i])
                return 0

            # Replay the reference's heap algorithm over the counts.
            results: list[tuple[int, int]] = []  # min-heap of (count, -id)
            out: list[Pair] = []

            def push(rid, cnt):
                heapq.heappush(results, (cnt, -rid))

            for rid, cnt in zip(cand_ids.tolist(),
                                cand_counts.tolist()):
                if cnt <= 0:
                    continue
                if tanimoto > 0:
                    if cnt <= min_tan or cnt >= max_tan:
                        continue
                elif cnt < opt.min_threshold:
                    continue
                if filters is not None:
                    attrs = (self.row_attr_store.attrs(rid)
                             if self.row_attr_store else None)
                    if not attrs:
                        continue
                    val = attrs.get(opt.filter_field)
                    if val is None or val not in filters:
                        continue
                if n == 0 or len(results) < n:
                    count = cnt if opt.src is None else src_count_of(rid)
                    if count == 0:
                        continue
                    if tanimoto > 0:
                        t = math.ceil(count * 100 / (cnt + src_count - count))
                        if t <= tanimoto:
                            continue
                    elif count < opt.min_threshold:
                        continue
                    push(rid, count)
                    if n > 0 and len(results) == n and opt.src is None:
                        break
                    continue
                threshold = results[0][0]
                if threshold < opt.min_threshold or cnt < threshold:
                    break
                count = src_count_of(rid)
                if count < threshold:
                    continue
                push(rid, count)

            while results:
                cnt, neg_id = heapq.heappop(results)
                out.append(Pair(-neg_id, cnt))
            out.reverse()
            return out

    def top_src(self, src_counts: np.ndarray, n: int,
                min_threshold: int) -> tuple[np.ndarray, np.ndarray]:
        """``top``'s plain with-source walk (n > 0, no attribute
        filter, no Tanimoto) fed ``src_counts`` — the source's
        intersection count with every row of this slice, by row id, as
        the device computed them (``Executor._topn_src_device``) — in
        place of ``_host_src_count_map``: the rank cache's candidates,
        the zero-overlap ones dropped as ``top`` drops them, then the
        same replay (``_top_src_arrays``). Returns (ids, counts) in
        (count desc, id asc) order."""
        with self._mu:
            self._verify_on_read()
            if self.tier is not None:
                self._tier_gate(full=True)
            self.cache.invalidate()
            cand_ids, cand_counts = self.cache.top_arrays()
        cand_ids = cand_ids.astype(np.int64)
        scnt = np.zeros(len(cand_ids), dtype=np.int64)
        inside = cand_ids < len(src_counts)
        scnt[inside] = src_counts[cand_ids[inside]]
        keep = scnt > 0
        if not keep.any():
            return self._EMPTY_COUNTS
        return self._top_src_arrays(cand_ids[keep],
                                    np.asarray(cand_counts)[keep],
                                    scnt[keep], n, min_threshold)

    @staticmethod
    def _top_src_vectorized(cand_ids, cand_counts, scnt, n: int,
                            min_threshold: int) -> list[Pair]:
        """``_top_src_arrays`` as Pairs."""
        ids, cnts = Fragment._top_src_arrays(cand_ids, cand_counts, scnt,
                                             n, min_threshold)
        return [Pair(int(i), int(c))
                for i, c in zip(ids.tolist(), cnts.tolist())]

    @staticmethod
    def _top_src_arrays(cand_ids, cand_counts, scnt, n: int,
                        min_threshold: int
                        ) -> tuple[np.ndarray, np.ndarray]:
        """Vectorized replay of the heap walk for the plain-src shape
        (gathered src counts in hand, no tanimoto, no attr filter,
        n>0). Exactly reproduces the loop's visit-order semantics,
        including the SUPERSET it returns for the cross-slice fill:
        phase A pushes the first n valid candidates (cache count and
        src count both >= max(min_threshold, 1)); t = their min src
        count; the walk then breaks at the first later candidate whose
        CACHE count drops below t, and pushes every candidate before
        that whose src count >= t. Output sorted (count desc, id asc),
        like the heap drain. Equivalence is pinned against a verbatim
        port of the loop by randomized parity in
        tests/test_fragment.py::TestTopSrcVectorizedParity."""
        floor = max(min_threshold, 1)
        scnt = np.asarray(scnt, dtype=np.int64)
        cache_ok = cand_counts >= floor
        valid = cache_ok & (scnt >= floor)
        valid_idx = np.flatnonzero(valid)
        if len(valid_idx) <= n:
            take = valid_idx
        else:
            first_n = valid_idx[:n]
            t = int(scnt[first_n].min())
            # Break at the first cache-valid candidate AFTER phase A
            # whose cache count < t (invalid-by-cache candidates are
            # skipped by `continue`, not `break`).
            later = np.flatnonzero(cache_ok)
            later = later[later > first_n[-1]]
            brk = later[cand_counts[later] < t]
            stop = int(brk[0]) if len(brk) else len(cand_ids)
            phase_b = later[(later < stop) & (scnt[later] >= t)]
            take = np.concatenate((first_n, phase_b))
        ids = cand_ids[take]
        cnts = scnt[take]
        order = np.lexsort((ids, -cnts))
        return ids[order], cnts[order]

    def recalculate_cache(self) -> None:
        """Rebuild the rank cache regardless of the invalidate rate limit
        (reference fragment.go:1059-1063)."""
        with self._mu:
            self.cache.recalculate()

    # -- block checksums / anti-entropy --------------------------------------

    def checksum(self) -> bytes:
        """Whole-fragment checksum = SHA1 over block checksums
        (reference fragment.go:679-687)."""
        h = hashlib.sha1()
        for blk in self.blocks():
            h.update(blk[1])
        return h.digest()

    def block_n(self) -> int:
        return self.storage.max() // (HASH_BLOCK_SIZE * SLICE_WIDTH)

    def invalidate_checksums(self) -> None:
        self.checksums.clear()

    def blocks(self) -> list[tuple[int, bytes]]:
        """(block_id, sha1) for all non-empty 100-row blocks
        (reference fragment.go:704-767). Hash = SHA1 of big-endian u64
        positions — wire-compatible with the reference's blockHasher."""
        with self._mu:
            values = self.storage.values()
            if not len(values):
                return []
            block_span = HASH_BLOCK_SIZE * SLICE_WIDTH
            block_ids = values // np.uint64(block_span)
            bounds = np.flatnonzero(np.diff(block_ids)) + 1
            starts = np.concatenate(([0], bounds))
            ends = np.concatenate((bounds, [len(values)]))
            out = []
            for s, e in zip(starts, ends):
                bid = int(block_ids[s])
                chk = self.checksums.get(bid)
                if chk is None:
                    chk = hashlib.sha1(
                        values[s:e].astype(">u8").tobytes()).digest()
                    self.checksums[bid] = chk
                out.append((bid, chk))
            return out

    def block_data(self, block_id: int) -> PairSet:
        """Bits in a block as (row, column-within-slice) arrays
        (reference fragment.go:785-795)."""
        with self._mu:
            span = HASH_BLOCK_SIZE * SLICE_WIDTH
            vals = self.storage.slice_range(block_id * span,
                                            (block_id + 1) * span)
            return PairSet(vals // np.uint64(SLICE_WIDTH),
                           vals % np.uint64(SLICE_WIDTH))

    def merge_block(self, block_id: int, data: list[PairSet]
                    ) -> tuple[list[PairSet], list[PairSet]]:
        """Majority-consensus merge of this block against peer copies
        (reference fragment.go:802-920, vectorized).

        Returns (sets, clears) diffs for each *peer* (local diffs are
        applied in place). A bit's final state is set iff ≥ half of the
        (len(data)+1) copies have it set.
        """
        for ps in data:
            if len(ps.row_ids) != len(ps.column_ids):
                raise ValueError("pair set mismatch")
        with self._mu:
            local = self.block_data(block_id)
            copies = [local] + list(data)
            min_row = block_id * HASH_BLOCK_SIZE
            max_row = (block_id + 1) * HASH_BLOCK_SIZE
            positions = []
            for ps in copies:
                keep = ((ps.row_ids >= min_row) & (ps.row_ids < max_row)
                        & (ps.column_ids < SLICE_WIDTH))
                # Dedup within each copy: a peer repeating a pair on the wire
                # must still get exactly one vote.
                positions.append(np.unique(
                    ps.row_ids[keep].astype(np.uint64) * np.uint64(SLICE_WIDTH)
                    + ps.column_ids[keep].astype(np.uint64)))
            all_pos = np.concatenate(positions) if positions else \
                np.empty(0, dtype=np.uint64)
            uniq, counts = np.unique(all_pos, return_counts=True)
            majority = (len(copies) + 1) // 2
            want = counts >= majority
            sets_out, clears_out = [], []
            local_set_pos = local_clear_pos = None
            for ps, pos in zip(copies, positions):
                has = np.isin(uniq, pos, assume_unique=True)
                to_set = uniq[want & ~has]
                to_clear = uniq[~want & has]
                if local_set_pos is None:  # first copy = local
                    local_set_pos, local_clear_pos = to_set, to_clear
                sets_out.append(PairSet(to_set // np.uint64(SLICE_WIDTH),
                                        to_set % np.uint64(SLICE_WIDTH)))
                clears_out.append(PairSet(to_clear // np.uint64(SLICE_WIDTH),
                                          to_clear % np.uint64(SLICE_WIDTH)))
            # Apply local diffs.
            need_snapshot = self._apply_merge_diffs(local_set_pos,
                                                    local_clear_pos)
        if need_snapshot:
            # Outside _mu: sync snapshot takes _snap_mu then _mu (see
            # import_bits for the ordering rationale).
            self.snapshot()
        else:
            # Per-bit path: records were group-appended; anti-entropy
            # acks the merge to peers, so commit before returning.
            self.wal_barrier()
        return sets_out[1:], clears_out[1:]

    # Above this many local diffs, per-bit WAL appends (plus a per-op
    # row-count cache update) cost more than one snapshot rewrite — the
    # same trade bulk import makes (fragment.go:924-989).
    MERGE_BULK_THRESHOLD = 256

    # src-TopN candidate sets up to this size use per-row roaring
    # intersections; larger walks take the one-pass vectorized map.
    SRC_MAP_MIN = 64


    def _apply_merge_diffs(self, set_pos: np.ndarray,
                           clear_pos: np.ndarray) -> None:
        """Apply a merge_block consensus diff locally. Small diffs go
        through the per-bit path (cheap WAL appends); large divergences
        bulk-apply with the op-log detached and one snapshot, so
        anti-entropy of a badly diverged replica does not crawl through
        a Python loop (reference bulk semantics: fragment.go:802-920)."""
        total = len(set_pos) + len(clear_pos)
        if total == 0:
            return False
        base_col = self.slice * SLICE_WIDTH
        if total <= self.MERGE_BULK_THRESHOLD:
            for pos in set_pos:
                self._mutate(int(pos) // SLICE_WIDTH,
                             base_col + int(pos) % SLICE_WIDTH, set=True)
            for pos in clear_pos:
                self._mutate(int(pos) // SLICE_WIDTH,
                             base_col + int(pos) % SLICE_WIDTH, set=False)
            return False  # per-bit path WALs every op; no snapshot due
        self._epoch += 1
        writer, self.storage.op_writer = self.storage.op_writer, None
        try:
            added = self.storage.add_many(set_pos)
            removed = self.storage.remove_many(clear_pos)
        finally:
            self.storage.op_writer = writer
        # Same per-bit side effects as _mutate, batched per row.
        rows = np.unique(np.concatenate((set_pos, clear_pos))
                         // np.uint64(SLICE_WIDTH))
        for rid in rows:
            rid = int(rid)
            self.checksums.pop(rid // HASH_BLOCK_SIZE, None)
            self.row_cache.invalidate(rid)
            self.device.invalidate_row(rid)
            cnt = self.row_count(rid)
            if (rid in self._row_counts
                    or len(self._row_counts) < _ROW_COUNT_CAP):
                self._row_counts[rid] = cnt
            self.cache.bulk_add(rid, cnt)
        self.cache.recalculate()
        if self.stats is not None:
            self.stats.count("setN", added)
            self.stats.count("clearN", removed)
        return True  # bulk path: caller snapshots outside _mu

    # -- iteration / export --------------------------------------------------

    def snapshot_value_chunks(self):
        """Point-in-time set positions, one sorted u64 array per
        container, safe to drain long after the call (e.g. by a WSGI
        layer streaming a CSV export). The fragment lock is held only
        while copying the COMPRESSED container buffers (u16 arrays /
        u64 words — bounded by on-disk size, not 8 B per set bit);
        expansion to positions happens lazily per yield, so neither
        lock-hold time nor peak memory scales with the rendered
        output. The reference streams exports bit-by-bit under its
        fragment mutex (handler.go:985-1025); this is the
        snapshot-then-stream equivalent."""
        with self._mu:
            snap = []
            for key, c in zip(list(self.storage.keys),
                              list(self.storage.containers)):
                if not c.n:
                    continue
                snap.append((int(key),
                             None if c.array is None else c.array.copy(),
                             None if c.bitmap is None else c.bitmap.copy(),
                             None if c.runs is None else c.runs.copy()))

        def expand():
            for key, arr, words, runs in snap:
                if runs is not None:
                    arr = roaring.runs_to_values(runs)
                elif arr is None:
                    arr = roaring.bitmap_words_to_values(words)
                yield np.uint64(key << 16) + arr.astype(np.uint64)
        return expand()

    def for_each_bit(self):
        """Yield (row_id, absolute_column_id) for every set bit."""
        base = self.slice * SLICE_WIDTH
        for pos in self.storage.values():
            pos = int(pos)
            yield pos // SLICE_WIDTH, base + pos % SLICE_WIDTH

    # -- backup / restore (reference fragment.go:1096-1266) ------------------

    def write_to(self, w) -> None:
        """Stream the fragment as a tar archive of 'data' (snapshot+WAL
        file bytes) and 'cache' (the TopN id sidecar). The data file is
        streamed from disk, not buffered — a full slice is 128 MB+.
        Size is captured under lock; the format is append-only so copying
        up to that size outside the lock is safe (fragment.go:1113-1155).
        """
        import tarfile
        self.flush_cache()
        self.wal_barrier()  # pending records must be inside the sized copy
        # Open the fd FIRST, then size it under lock: a concurrent
        # snapshot() os.replace()s the path, but this fd pins the old
        # inode, which only ever grows by appended ops — so copying
        # exactly fstat-size bytes from it is a consistent snapshot+WAL
        # prefix (same trick as fragment.go:1113-1151).
        tw = tarfile.open(fileobj=w, mode="w|")
        with open(self.path, "rb") as f:
            with self._mu:
                data_size = os.fstat(f.fileno()).st_size
            info = tarfile.TarInfo("data")
            info.size = data_size
            info.mode = 0o600
            tw.addfile(info, CappedReader(f, data_size))
        try:
            with open(self.cache_path, "rb") as f:
                cache_size = os.fstat(f.fileno()).st_size
                cinfo = tarfile.TarInfo("cache")
                cinfo.size = cache_size
                cinfo.mode = 0o600
                tw.addfile(cinfo, f)
        except FileNotFoundError:
            cinfo = tarfile.TarInfo("cache")
            cinfo.size = 0
            cinfo.mode = 0o600
            tw.addfile(cinfo)
        tw.close()

    def read_from(self, r) -> None:
        """Restore from a write_to tar stream: replace the data file,
        reopen storage, reload the cache. Entries stream to disk in
        chunks."""
        import shutil
        import tarfile
        tr = tarfile.open(fileobj=r, mode="r|")
        import io
        # _snap_mu first (lock order): a late worker must not splice a
        # stale pre-restore snapshot over the restored file.
        with self._snap_mu, self._mu:
            for info in tr:
                src = tr.extractfile(info) or io.BytesIO()
                if info.name == "data":
                    self._close_storage()
                    tmp = self.path + ".restoring"
                    try:
                        with open(tmp, "wb") as f:
                            shutil.copyfileobj(src, f)
                            f.flush()
                            os.fsync(f.fileno())
                        os.replace(tmp, self.path)
                    except BaseException:
                        # A truncated source (aborted upload) must not
                        # leave the fragment with storage closed — the
                        # old data file is still in place; reopen it.
                        self._open_storage()
                        raise
                    self._open_storage()
                    self._epoch += 1
                    self._row_counts.clear()
                    self.row_cache.clear()
                    self.device.invalidate_all()
                    self.checksums.clear()
                elif info.name == "cache":
                    with open(self.cache_path, "wb") as f:
                        shutil.copyfileobj(src, f)
                    self.cache = cache_mod.new_cache(self.cache_type,
                                                     self.cache_size)
                    self._cache_flushed = None  # sidecar replaced
                    self._open_cache()
                else:
                    raise PilosaError(f"invalid fragment archive file:"
                                      f" {info.name!r}")

    # -- cache persistence ---------------------------------------------------

    def flush_cache(self) -> None:
        """Persist cache ids to the .cache protobuf sidecar
        (reference fragment.go:1067-1093). Skips the write when the
        serialized blob matches the last flush — the sidecar is
        already those bytes, and repeated backup/stream passes must
        not pay (or hold ``_mu`` across) an fsync per fragment."""
        with self._mu:
            if self.cache is None:
                return
            blob = pb.Cache(IDs=self.cache.ids()).SerializeToString()
            if blob == self._cache_flushed:
                return
            tmp = self.cache_path + ".tmp"
            with open(tmp, "wb") as f:
                f.write(blob)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self.cache_path)
            self._cache_flushed = blob
