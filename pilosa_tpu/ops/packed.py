"""Packed dense bitmap layout — the device-side data representation.

The reference's compute walks compressed roaring containers with scalar/SIMD
loops (roaring.go:1192-1558 + assembly_amd64.s). TPUs want dense, regular,
vectorized data: here a fragment's rows live in HBM as a row-major
``uint32[n_rows, 32768]`` matrix — 2^20 columns / 32 bits per word — and all
set algebra is elementwise ops over whole rows (pilosa_tpu.ops.kernels).

u32 is the natural TPU word (native lane type; XLA has no u64 popcount
advantage), and the layout lines up with the storage format for free: a
roaring bitmap container is 1024 little-endian u64 words covering a 2^16
position range, which reinterpret as exactly the 2048 little-endian u32
device words of that range — so packing a dense container is a memcpy, no
bit manipulation.

Column ids are u64 host-side (positions up to 2^64); the device only ever
sees word indices within a slice, which fit comfortably in i32.
"""

from __future__ import annotations

import numpy as np

from .. import SLICE_WIDTH
from ..storage import native
from ..storage.roaring import Bitmap, runs_to_words

WORD_BITS = 32
# u32 words per slice row: 2^20 / 32 = 32768 (a multiple of the 128-lane
# TPU tile, so rows map onto the VPU with no padding).
WORDS_PER_SLICE = SLICE_WIDTH // WORD_BITS
# u32 words per roaring container range (2^16 positions / 32).
_WORDS_PER_CONTAINER = (1 << 16) // WORD_BITS


def pack_bitmap(b: Bitmap, n_words: int, out: np.ndarray | None = None,
                base_word: int = 0) -> np.ndarray:
    """Pack a roaring bitmap into a dense u32 word vector.

    ``b``'s positions are interpreted relative to ``base_word * 32``; words
    outside [0, n_words) are ignored. Dense containers blit via u64→u32
    reinterpretation; array containers scatter.
    """
    if out is None:
        out = np.zeros(n_words, dtype=np.uint32)
    for key, c in zip(b.keys, b.containers):
        if c.n == 0:
            continue
        word0 = key * _WORDS_PER_CONTAINER - base_word
        if word0 >= n_words or word0 + _WORDS_PER_CONTAINER <= 0:
            continue
        if not c.is_array():
            dst0, dst1 = max(word0, 0), min(word0 + _WORDS_PER_CONTAINER,
                                            n_words)
            # Run containers decode to dense words here — the device
            # residency upload path (parallel.residency leaf_slab /
            # candidate_block) sees bit-plane slabs regardless of the
            # host storage kind.
            words64 = (c.bitmap if c.bitmap is not None
                       else runs_to_words(c.runs))
            src = words64.view("<u4")[dst0 - word0:dst1 - word0]
            out[dst0:dst1] |= src
        else:
            a = c.array
            widx = word0 + (a >> np.uint32(5)).astype(np.int64)
            keep = (widx >= 0) & (widx < n_words)
            np.bitwise_or.at(out, widx[keep],
                             np.uint32(1) << (a[keep] & np.uint32(31)))
    return out


def pack_storage_row(storage: Bitmap, row_id: int,
                     out: np.ndarray) -> np.ndarray:
    """Pack one row of a fragment-local storage bitmap into dense words.

    ``storage`` holds positions ``pos = row * SLICE_WIDTH + col`` (the
    fragment bit layout, reference fragment.go:1511-1514); the result is
    the dense words of columns [0, 2^20) of that row.
    """
    row_bm = storage.offset_range(0, row_id * SLICE_WIDTH,
                                  (row_id + 1) * SLICE_WIDTH)
    return pack_bitmap(row_bm, out.shape[-1], out=out)


def pack_rows(storage: Bitmap, row_ids) -> np.ndarray:
    """Pack rows of a fragment-local storage bitmap into u32[n, 32768]."""
    row_ids = list(row_ids)
    out = np.zeros((len(row_ids), WORDS_PER_SLICE), dtype=np.uint32)
    for i, row in enumerate(row_ids):
        pack_storage_row(storage, row, out[i])
    return out


def unpack_words(words: np.ndarray) -> np.ndarray:
    """Dense u32 word vector → sorted u64 bit positions (host)."""
    return native.unpack_words(np.ascontiguousarray(words))


def unpack_to_bitmap(words: np.ndarray, base_word: int = 0) -> Bitmap:
    """Dense u32 word vector → roaring bitmap with positions offset by
    ``base_word * 32``.

    Container-direct build: the dense vector IS the container layout
    (2048 u32 words per 2^16-value container), so dense containers
    become zero-copy u64 views of the fetched array and only sparse
    ones expand to value arrays — the expand-every-position
    ``from_sorted`` path cost ~8 B/bit plus a full re-merge, which was
    most of the device materialize leg's repack time. Requires container alignment (base_word and len multiples of
    2048), which every device block satisfies; anything else falls
    back to the general path."""
    from ..storage.roaring import (ARRAY_MAX_SIZE, Container,
                                   bitmap_words_to_values)
    per_container = _WORDS_PER_CONTAINER  # 2048 u32 words
    if (base_word % per_container or len(words) % per_container
            or words.dtype != np.uint32 or not words.flags.c_contiguous):
        pos = unpack_words(words)
        if base_word:
            pos = pos + np.uint64(base_word * WORD_BITS)
        return Bitmap.from_sorted(pos)
    counts = np.bitwise_count(words).astype(np.int64) \
        .reshape(-1, per_container).sum(axis=1)
    b = Bitmap()
    base_key = base_word // per_container
    w64 = words.view("<u8").reshape(-1, _WORDS_PER_CONTAINER // 2)
    for ci in np.flatnonzero(counts).tolist():
        n = int(counts[ci])
        span64 = w64[ci]
        if n > ARRAY_MAX_SIZE:
            # Zero-copy view into the fetched block, COW-marked: the
            # block outlives the bitmap via the view references.
            c = Container.from_bitmap(span64, n=n, mapped=True)
        else:
            c = Container.from_array(bitmap_words_to_values(span64))
        b.keys.append(base_key + ci)
        b.containers.append(c)
    return b


# 128 words per bucket group - must match pallas_kernels._DENSIFY_LANES.
_DENSIFY_LANES = 128
# The widest bucket that ships sparse: at 32 slots a group the bucketed
# payload (8 B a slot) is half the dense block's (4 B a word), the margin
# the transfer must win by, and the kernel's VMEM envelope ends there;
# sparse loses outright by 128 (every slot of every group is shipped and
# OR-ed). Must match parallel.mesh.DENSIFY_WIDTHS.
_MAX_SLOTS = 32


def _array_words(arrays: list, slots: list, index_t) -> tuple:
    """Set words of array containers, each at container slot
    ``slots[i]`` of the slab: (ascending slab word indices, u32 values).
    Values are sorted in a container and containers come in slab order,
    so equal word indices are adjacent: one reduceat ORs each word's
    bits together."""
    low = np.concatenate(arrays)
    at = np.repeat(np.asarray(slots, dtype=index_t) * _WORDS_PER_CONTAINER,
                   [len(a) for a in arrays])
    at += low >> np.uint32(5)
    new_word = np.ones(len(at), dtype=bool)
    np.not_equal(at[1:], at[:-1], out=new_word[1:])
    first = np.flatnonzero(new_word)
    low &= np.uint32(31)
    return at[first], np.bitwise_or.reduceat(np.uint32(1) << low, first)


def pack_slab(rows: list, sparse: bool = True
              ) -> tuple[tuple | None, np.ndarray | None, int]:
    """One pass over every container of a slab: the host side of a
    residency fill (parallel.residency._fill).

    ``rows`` has one entry a slice-row: None (absent = zero words) or
    the row's containers as a Bitmap keyed 0..15 (what
    ``Fragment.row_containers`` and ``unpack_to_bitmap`` give). Returns
    ``(sparse, block, containers)``, one of the first two None:

    - ``sparse``: ``([T, 256, G] u32 lanes, same-shape u32 values)``,
      the upload payload of the device densify kernel
      (ops.pallas_kernels.densify_pallas). Slot g of 128-word group s of
      slice-row t is one set word (its lane 0-127 and its value) in
      ascending word order; ``val == 0`` slots are padding. G is the
      largest count of set words in any group of any slice-row, rounded
      up to a power of two (shape-bucketing keeps the kernel's compile
      cache small). Taken when ``sparse`` allows it and G is at most
      ``_MAX_SLOTS``.
    - ``block``: dense ``[T, 32768]`` u32 otherwise.

    Where the native library loaded (``storage.native.available()``) the
    pass is one call into it, which lets go of the interpreter once: the
    walk here only lists each container's slot, kind and buffer. The
    numpy pass answers otherwise, array for array the same."""
    if native.available():
        return _pack_slab_native(rows, sparse)
    return _pack_slab_numpy(rows, sparse)


# The element type of a container buffer, by the kind the native pack's
# table gives it: 0 array, 1 bitmap, 2 run.
_KIND_DTYPES = (np.dtype(np.uint32), np.dtype(np.uint64),
                np.dtype(np.uint16))
# Array containers of at most this many values reach the native pack
# concatenated (kind 3), in one numpy call that keeps the interpreter
# (numpy lets go of it only to copy an input of more than 500 values):
# taking a buffer's address costs more than copying that many.
_POOLED = 500


def _pack_slab_native(rows: list, sparse: bool) -> tuple:
    per = WORDS_PER_SLICE // _WORDS_PER_CONTAINER
    table, bufs, pooled, pool_n = [], [], [], 0
    for t, row in enumerate(rows):
        if row is None:
            continue
        slot0 = t * per
        for key, c in zip(row.keys, row.containers):
            if not c.n:
                continue
            if c.bitmap is not None:
                kind, buf = 1, c.bitmap
            elif c.runs is not None:
                kind, buf = 2, c.runs
            else:
                kind, buf = 0, c.array
                if len(buf) <= _POOLED:
                    pooled.append(buf)
                    table += (slot0 + key, 3, pool_n, len(buf))
                    pool_n += len(buf)
                    continue
            if buf.dtype is not _KIND_DTYPES[kind] or \
                    not buf.flags.c_contiguous:
                buf = np.ascontiguousarray(buf, dtype=_KIND_DTYPES[kind])
            # The list, not the address, keeps a mapped buffer (and with
            # it the fragment's map) alive until the call has returned.
            bufs.append(buf)
            table += (slot0 + key, kind, buf.ctypes.data, buf.size)
    pool = (np.concatenate(pooled, dtype=np.uint32)
            if pooled else np.empty(0, np.uint32))
    out = np.empty((len(rows), WORDS_PER_SLICE), dtype=np.uint32)
    g_pad = native.pack_slab(np.array(table, dtype=np.int64).reshape(-1, 4),
                             pool, out, _MAX_SLOTS if sparse else 0)
    taken = len(bufs) + len(pooled)
    if not g_pad:
        return None, out, taken
    m = len(rows) * (WORDS_PER_SLICE // _DENSIFY_LANES) * g_pad
    flat, shape = out.reshape(-1), (len(rows), -1, g_pad)
    return (flat[:m].reshape(shape), flat[m:2 * m].reshape(shape)), None, \
        taken


def _pack_slab_numpy(rows: list, sparse: bool) -> tuple:
    """``pack_slab`` without the native library. The numpy calls do not
    grow with the slab: array containers are concatenated and grouped
    by word once, bitmap and run containers are stacked once as the u32
    words they are (a dense block takes them by one assignment, never
    through pairs), one bincount finds the ranks, one scatter fills the
    buckets."""
    per = _WORDS_PER_CONTAINER
    arrays, array_slots, dense, dense_slots = [], [], [], []
    for t, row in enumerate(rows):
        if row is None:
            continue
        slot0 = t * (WORDS_PER_SLICE // per)
        for key, c in zip(row.keys, row.containers):
            if not c.n:
                continue
            if c.is_array():
                arrays.append(c.array)
                array_slots.append(slot0 + key)
            else:
                dense.append((c.bitmap if c.bitmap is not None
                              else runs_to_words(c.runs)).view("<u4"))
                dense_slots.append(slot0 + key)
    taken = len(arrays) + len(dense)
    # A slab word index fits 32 bits up to 2^17 slice-rows: half the
    # bytes through every step of a 256-slice fill's ten million values.
    index_t = np.uint32 if len(rows) <= 1 << 17 else np.int64
    if arrays:
        word, val = _array_words(arrays, array_slots, index_t)
    else:
        word, val = np.empty(0, index_t), np.empty(0, np.uint32)
    if dense:
        stack = np.concatenate(dense).reshape(-1, per)
    if sparse:      # the gate: the fullest group's set words
        fullest = max(
            1, int(np.bincount(word >> 7).max()) if len(word) else 0,
            int(np.count_nonzero(stack.reshape(-1, _DENSIFY_LANES),
                                 axis=1).max()) if dense else 0)
        g_pad = 1 << (fullest - 1).bit_length()
        sparse = g_pad <= _MAX_SLOTS
    if not sparse:
        block = np.zeros((len(rows), WORDS_PER_SLICE), dtype=np.uint32)
        block.reshape(-1)[word] = val
        if dense:
            block.reshape(-1, per)[dense_slots] = stack
        return None, block, taken
    if dense:
        # A slab holding bitmap or run containers that still passes the
        # gate (a short run does): their set words join the arrays'.
        d, w = np.nonzero(stack)
        word = np.concatenate(
            (word, (np.asarray(dense_slots)[d] * per + w).astype(index_t)))
        val = np.concatenate((val, stack[d, w]))
        order = np.argsort(word, kind="stable")
        word, val = word[order], val[order]
    group = word >> 7
    counts = np.bincount(group, minlength=len(rows) * WORDS_PER_SLICE
                         // _DENSIFY_LANES)
    rank = np.arange(len(word)) - (np.cumsum(counts) - counts)[group]
    lanes = np.zeros((len(counts), g_pad), dtype=np.uint32)
    vals = np.zeros((len(counts), g_pad), dtype=np.uint32)
    lanes[group, rank] = word & 127
    vals[group, rank] = val
    shape = (len(rows), -1, g_pad)
    return (lanes.reshape(shape), vals.reshape(shape)), None, taken
