"""View: container of fragments by slice for one orientation/time granularity.

Reference: view.go. Names: ``standard``, ``inverse``, plus time-suffixed
variants (``standard_2017``, ``standard_201701``, ...). Directory layout
``<frame>/views/<name>/fragments/<slice>`` (view.go:186-189). Creating a
fragment for a new max slice notifies the cluster via the on_create_slice
hook (view.go:219-254 broadcasts CreateSliceMessage).
"""

from __future__ import annotations

import os
import threading
from typing import Callable, Optional

from .. import SLICE_WIDTH
from ..parallel.residency import device_cache, next_uid
from ..storage import cache as cache_mod
from ..storage.fragment import Fragment
from ..utils import logger as logger_mod
from ..utils.stats import NOP

VIEW_STANDARD = "standard"
VIEW_INVERSE = "inverse"
# BSI integer-field views: one per field, column-sharded like standard
# (pilosa 1.0's viewFieldPrefix).
VIEW_FIELD_PREFIX = "field_"


def is_inverse_view(name: str) -> bool:
    return name.startswith(VIEW_INVERSE)


def is_field_view(name: str) -> bool:
    return name.startswith(VIEW_FIELD_PREFIX)


def field_view_name(field: str) -> str:
    return VIEW_FIELD_PREFIX + field


def is_valid_view(name: str) -> bool:
    return (name.startswith(VIEW_STANDARD)
            or name.startswith(VIEW_INVERSE)
            or is_field_view(name))


class View:
    def __init__(self, path: str, index: str, frame: str, name: str,
                 cache_type: str = cache_mod.DEFAULT_CACHE_TYPE,
                 cache_size: int = cache_mod.DEFAULT_CACHE_SIZE,
                 row_attr_store=None,
                 on_create_slice: Optional[Callable[[int], None]] = None,
                 stats=NOP, logger=logger_mod.NOP, quarantine=None):
        self.logger = logger
        self.quarantine = quarantine  # holder's QuarantineRegistry
        self.path = path
        self.index = index
        self.frame = frame
        self.name = name
        self.cache_type = cache_type
        self.cache_size = cache_size
        self.row_attr_store = row_attr_store
        self.on_create_slice = on_create_slice
        self.stats = stats
        self.fragments: dict[int, Fragment] = {}
        self._max_slice = 0
        self._mu = threading.RLock()
        # The view-level mutation token (docs/OBSERVABILITY.md "The
        # view token"): ``generation`` moves whenever the resident
        # picture of any fragment of this view may have — wherever a
        # fragment's own device generation moves, and when a fragment
        # is created, opened, closed or snapshotted; a view dropped and
        # recreated is a new object with a fresh ``uid``. The executor's
        # route records, the plan memo and TopN's candidate blocks embed
        # the pair, so a read validates a whole view with one compare.
        self.uid = next_uid()
        self.generation = 0
        self._token_mu = threading.Lock()
        # A leaf slab's token is its ROW's (``row_token``): the
        # generation of the view's last whole retirement (an import, a
        # reopen) and of the last write to the row.
        self.epoch = 0
        self.row_epochs: dict[int, int] = {}
        # The generation of the last write to any row: with ``epoch``,
        # what a whole-frame device form is keyed on (``frame_token``)
        # — a snapshot moves ``generation`` and changes no bit.
        self.last_row_write = 0

    def bump(self) -> None:
        """Move the token. Writers call this AFTER changing the data
        (or the fragment map) and BEFORE acknowledging; readers read
        ``generation`` BEFORE the data. Under the lock, so that racing
        writers of two fragments cannot store the counter backwards —
        a reader compares for equality and needs it monotonic."""
        with self._token_mu:
            self.generation += 1

    def row_token(self, row_id: int) -> tuple[int, int]:
        """What a leaf slab of ``row_id`` is keyed on beside the uid
        (the executor reads the two fields inline)."""
        return (self.epoch, self.row_epochs.get(row_id, 0))

    def frame_token(self) -> tuple[int, int]:
        """What the view's whole-frame device form is keyed on beside
        the uid: moved by every write to any row and every retirement
        of the whole view, not by a snapshot."""
        return (self.epoch, self.last_row_write)

    def retire_rows(self, row_ids) -> int:
        """A write changed bits of ``row_ids``: move the token and those
        rows' tokens, then drop their resident slabs (the others stay).
        Same order as ``bump``: after the data, before the ack."""
        with self._token_mu:
            self.generation += 1
            gen = self.last_row_write = self.generation
            epochs = self.row_epochs
            for r in row_ids:
                epochs[r] = gen
        return device_cache().retire_rows(self.uid, row_ids)

    def retire_all(self) -> int:
        """Every row may have changed (an import, a reopen): move the
        token and every row's, then drop the view's resident slabs."""
        with self._token_mu:
            self.generation += 1
            self.epoch = self.generation
        return device_cache().retire_view(self.uid)

    # -- lifecycle

    @property
    def fragments_path(self) -> str:
        return os.path.join(self.path, "fragments")

    def fragment_path(self, slice: int) -> str:
        return os.path.join(self.fragments_path, str(slice))

    def open(self) -> None:
        with self._mu:
            os.makedirs(self.fragments_path, exist_ok=True)
            for entry in sorted(os.listdir(self.fragments_path)):
                if entry.endswith(".blob") and entry[:-5].isdigit():
                    # Blob-tier stub (pilosa_tpu.tier): the data file
                    # left local disk, but the fragment must stay
                    # discoverable — Fragment.open recognizes the
                    # stub and opens in the blob state.
                    entry = entry[:-5]
                elif not entry.isdigit():
                    continue
                slice = int(entry)
                if slice in self.fragments:
                    continue
                frag = self._new_fragment(slice)
                frag.open()
                self.fragments[slice] = frag
            self._max_slice = max(self.fragments, default=0)
            self.bump()  # after the map holds every opened fragment

    def close(self) -> None:
        with self._mu:
            for frag in self.fragments.values():
                frag.close()
            self.fragments.clear()
            self.bump()  # after the map is empty

    def _new_fragment(self, slice: int) -> Fragment:
        frag = Fragment(self.fragment_path(slice), self.index, self.frame,
                        self.name, slice, cache_type=self.cache_type,
                        cache_size=self.cache_size,
                        row_attr_store=self.row_attr_store,
                        stats=self.stats.with_tags(f"slice:{slice}"),
                        logger=self.logger, quarantine=self.quarantine)
        frag.device.owner = self
        return frag

    # -- fragments

    def fragment(self, slice: int) -> Optional[Fragment]:
        return self.fragments.get(slice)

    def create_fragment_if_not_exists(self, slice: int) -> Fragment:
        with self._mu:
            frag = self.fragments.get(slice)
            if frag is not None:
                return frag
            frag = self._new_fragment(slice)
            frag.open()
            # Announce only when the max slice grows (view.go:232-246).
            if slice > self._max_slice:
                self._max_slice = slice
                if self.on_create_slice is not None:
                    self.on_create_slice(slice)
            self.fragments[slice] = frag
            self.bump()  # after the map holds it: a walk that missed
            # the fragment read the token before this bump
            self.stats.count("maxSlice", 1)
            return frag

    def max_slice(self) -> int:
        with self._mu:
            return max(self._max_slice, max(self.fragments, default=0))

    # -- bit ops (route column → slice; view.go:265-283)

    def set_bit(self, row_id: int, column_id: int) -> bool:
        frag = self.create_fragment_if_not_exists(column_id // SLICE_WIDTH)
        return frag.set_bit(row_id, column_id)

    def clear_bit(self, row_id: int, column_id: int) -> bool:
        frag = self.create_fragment_if_not_exists(column_id // SLICE_WIDTH)
        return frag.clear_bit(row_id, column_id)

    def mutate_bits(self, row_ids: np.ndarray, column_ids: np.ndarray,
                    set: bool) -> np.ndarray:
        """Batched set/clear through the fragments' native batch engine:
        one stable argsort groups the ops by slice, one batched mutation
        per touched fragment. Returns a per-op changed bool array (WAL'd
        durability identical to the per-op path — fragment.set_bits)."""
        import numpy as _np
        rows = _np.asarray(row_ids, dtype=_np.uint64)
        cols = _np.asarray(column_ids, dtype=_np.uint64)
        changed = _np.zeros(len(rows), dtype=bool)
        if not len(rows):
            return changed
        slices = cols // _np.uint64(SLICE_WIDTH)
        order = _np.argsort(slices, kind="stable")
        srt = slices[order]
        bounds = _np.flatnonzero(srt[1:] != srt[:-1]) + 1
        starts = _np.concatenate(([0], bounds, [len(srt)]))
        w = _np.uint64(SLICE_WIDTH)
        for s, e in zip(starts[:-1].tolist(), starts[1:].tolist()):
            idx = order[s:e]
            frag = self.create_fragment_if_not_exists(int(srt[s]))
            op = frag.set_bits if set else frag.clear_bits
            ch_pos = op(rows[idx], cols[idx])
            if len(ch_pos):
                pos = rows[idx] * w + cols[idx] % w
                # Only the FIRST occurrence of a duplicated op changed
                # (per-op semantics: the repeat is an idempotent no-op).
                uniq, first = _np.unique(pos, return_index=True)
                hit = _np.isin(uniq, ch_pos, assume_unique=True)
                changed[idx[first[hit]]] = True
        return changed
