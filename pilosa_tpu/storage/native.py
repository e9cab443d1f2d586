"""ctypes loader for the native host bit kernels (pilosa_tpu/native/bitops.cpp)
with pure-numpy fallbacks.

Mirrors the reference's build-tag dispatch between assembly and generic Go
popcount (roaring/assembly_asm.go / assembly_generic.go): the native library
is built on first use with g++ and cached under ``utils.cache_dir()``; if the
build fails every entry point falls back to vectorized numpy, the failure is
logged as an error and ``available()`` (the ``/status`` ``build.native`` flag)
says so.

The bulk entry points take a whole table of containers in one crossing, so
the interpreter is let go of once a call: ``batch_add`` / ``batch_remove``
(a write batch), ``write_snapshot_fd`` (a snapshot),
``bitmap_intersection_count`` (a host count) and ``pack_slab`` (the pack
of a residency fill, ops.packed).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(__file__)), "native")
_SRC = os.path.join(_NATIVE_DIR, "bitops.cpp")

_lib = None
_lib_lock = threading.Lock()
_load_failed = False


def _so_path() -> str:
    # Keyed by source content hash AND machine: the binary is
    # -march=native, so a stale .so from another source revision, or one
    # built on another CPU and copied here with the checkout, must never
    # load (SIGILL). Never ship the artifact, always rebuild per
    # (machine, source).
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    from ..utils import cache_dir, machine_tag
    cache = cache_dir()
    os.makedirs(cache, exist_ok=True)
    return os.path.join(cache, f"libbitops-{digest}-{machine_tag()}.so")


def _load():
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    with _lib_lock:
        if _lib is not None or _load_failed:
            return _lib
        try:
            so = _so_path()
            if not os.path.exists(so):
                subprocess.run(
                    ["g++", "-O3", "-march=native", "-shared", "-fPIC",
                     "-o", so + ".tmp", _SRC],
                    check=True, capture_output=True)
                os.replace(so + ".tmp", so)
            lib = ctypes.CDLL(so)
            _declare(lib)
            _lib = lib
        except Exception as e:  # noqa: BLE001 - numpy paths still answer
            _load_failed = True
            _log_build_failure("libbitops", e)
        return _lib


def _log_build_failure(what: str, exc: Exception) -> None:
    """A native library that did not build leaves the slow numpy/Python
    paths serving: say so once, loudly, with the compiler's own words
    (/status ``build.native``/``build.nativeExt`` carry the outcome)."""
    import logging
    detail = getattr(exc, "stderr", b"") or b""
    logging.getLogger("pilosa_tpu.native").error(
        "native library %s failed to build or load (%s: %s); serving"
        " from the pure-Python fallback\n%s", what,
        type(exc).__name__, exc,
        detail.decode("utf-8", "replace")[-2000:])


def _declare(lib):
    # Every op takes raw void* params: a million tiny container calls
    # per query pay ~4 us each for ctypes.data_as + cast, vs ~0.4 us to
    # read the buffer address from __array_interface__, and data_as
    # leaves a reference cycle (two objects a pointer) for the
    # collector's passes on every call. The per-container wrappers
    # check dtype/contiguity and pass plain ints (c_void_p accepts
    # them); the bulk ops' callers size and type their arrays.
    vp = ctypes.c_void_p
    i64 = ctypes.c_int64
    for name in ("popcnt_and", "popcnt_or", "popcnt_xor", "popcnt_andnot"):
        fn = getattr(lib, name)
        fn.argtypes = [vp, vp, i64]
        fn.restype = ctypes.c_uint64
    lib.popcnt.argtypes = [vp, i64]
    lib.popcnt.restype = ctypes.c_uint64
    lib.intersect_sorted_u32.argtypes = [vp, i64, vp, i64, vp]
    lib.intersect_sorted_u32.restype = i64
    lib.intersection_count_sorted_u32.argtypes = [vp, i64, vp, i64]
    lib.intersection_count_sorted_u32.restype = i64
    lib.union_sorted_u32.argtypes = [vp, i64, vp, i64, vp]
    lib.union_sorted_u32.restype = i64
    lib.difference_sorted_u32.argtypes = [vp, i64, vp, i64, vp]
    lib.difference_sorted_u32.restype = i64
    lib.pack_positions_u32.argtypes = [vp, i64, ctypes.c_uint64, i64, vp]
    lib.pack_positions_u32.restype = None
    lib.bench_setbit.argtypes = [ctypes.c_char_p, vp, i64, i64]
    lib.bench_setbit.restype = i64
    lib.unpack_words_u32.argtypes = [vp, i64, vp]
    lib.unpack_words_u32.restype = i64
    lib.batch_add.argtypes = [i64] + [vp] * 14 + [i64]
    lib.batch_add.restype = i64
    lib.batch_remove.argtypes = [i64] + [vp] * 12 + [i64]
    lib.batch_remove.restype = i64
    lib.write_snapshot_fd.argtypes = [ctypes.c_int, i64, vp, vp, vp, vp]
    lib.write_snapshot_fd.restype = i64
    lib.bitmap_intersection_count.argtypes = [
        i64, vp, vp, vp, vp, i64, vp, vp, vp, vp]
    lib.bitmap_intersection_count.restype = i64
    lib.parse_csv_u64_pairs.argtypes = [ctypes.c_char_p, i64, vp, vp, i64]
    lib.parse_csv_u64_pairs.restype = i64
    lib.pack_slab_u32.argtypes = [i64, vp, i64, i64, vp, vp]
    lib.pack_slab_u32.restype = i64


def _ptr(a: np.ndarray) -> int:
    """The buffer address of ``a``, which the caller keeps alive."""
    return a.__array_interface__["data"][0]


def _contig(a: np.ndarray, dtype) -> np.ndarray:
    return np.ascontiguousarray(a, dtype=dtype)


_U32 = np.dtype(np.uint32)
_U64 = np.dtype(np.uint64)


def _addr32(a: np.ndarray) -> tuple[int, np.ndarray]:
    """(buffer address, the array actually addressed) for the raw
    void* calling convention; normalizes dtype/layout only when
    needed (the hot container arrays are always contiguous u32)."""
    if a.dtype is not _U32 and a.dtype != _U32 or not a.flags.c_contiguous:
        a = np.ascontiguousarray(a, dtype=np.uint32)
    return a.__array_interface__["data"][0], a


def _addr64(a: np.ndarray) -> tuple[int, np.ndarray]:
    if a.dtype is not _U64 and a.dtype != _U64 or not a.flags.c_contiguous:
        a = np.ascontiguousarray(a, dtype=np.uint64)
    return a.__array_interface__["data"][0], a


# ---- public API -------------------------------------------------------------


def popcnt_and(a: np.ndarray, b: np.ndarray) -> int:
    lib = _load()
    if lib is not None:
        pa, a = _addr64(a)
        pb, b = _addr64(b)
        return int(lib.popcnt_and(pa, pb, len(a)))
    return int(np.bitwise_count(a & b).sum())


def popcnt_or(a: np.ndarray, b: np.ndarray) -> int:
    lib = _load()
    if lib is not None:
        pa, a = _addr64(a)
        pb, b = _addr64(b)
        return int(lib.popcnt_or(pa, pb, len(a)))
    return int(np.bitwise_count(a | b).sum())


def popcnt_xor(a: np.ndarray, b: np.ndarray) -> int:
    lib = _load()
    if lib is not None:
        pa, a = _addr64(a)
        pb, b = _addr64(b)
        return int(lib.popcnt_xor(pa, pb, len(a)))
    return int(np.bitwise_count(a ^ b).sum())


def popcnt_andnot(a: np.ndarray, b: np.ndarray) -> int:
    lib = _load()
    if lib is not None:
        pa, a = _addr64(a)
        pb, b = _addr64(b)
        return int(lib.popcnt_andnot(pa, pb, len(a)))
    return int(np.bitwise_count(a & ~b).sum())


def popcnt(a: np.ndarray) -> int:
    lib = _load()
    if lib is not None:
        pa, a = _addr64(a)
        return int(lib.popcnt(pa, len(a)))
    return int(np.bitwise_count(a).sum())


def intersect_sorted_u32(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    lib = _load()
    if lib is not None:
        pa, a = _addr32(a)
        pb, b = _addr32(b)
        out = np.empty(min(len(a), len(b)), dtype=np.uint32)
        n = lib.intersect_sorted_u32(pa, len(a), pb, len(b),
                                     out.__array_interface__["data"][0])
        return out[:n]
    return np.intersect1d(a, b, assume_unique=True).astype(np.uint32)


def intersection_count_sorted_u32(a: np.ndarray, b: np.ndarray) -> int:
    lib = _load()
    if lib is not None:
        pa, a = _addr32(a)
        pb, b = _addr32(b)
        return int(lib.intersection_count_sorted_u32(pa, len(a),
                                                     pb, len(b)))
    return len(np.intersect1d(a, b, assume_unique=True))


def union_sorted_u32(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    lib = _load()
    if lib is not None:
        pa, a = _addr32(a)
        pb, b = _addr32(b)
        out = np.empty(len(a) + len(b), dtype=np.uint32)
        n = lib.union_sorted_u32(pa, len(a), pb, len(b),
                                 out.__array_interface__["data"][0])
        return out[:n]
    return np.union1d(a, b).astype(np.uint32)


def difference_sorted_u32(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    lib = _load()
    if lib is not None:
        pa, a = _addr32(a)
        pb, b = _addr32(b)
        out = np.empty(len(a), dtype=np.uint32)
        n = lib.difference_sorted_u32(pa, len(a), pb, len(b),
                                      out.__array_interface__["data"][0])
        return out[:n]
    return np.setdiff1d(a, b, assume_unique=True).astype(np.uint32)


def pack_positions(positions: np.ndarray, slice_width: int,
                   words_per_row: int, words: np.ndarray) -> None:
    """Scatter u64 bit positions into a row-major u32 word matrix in place."""
    if words.dtype != np.uint32 or not words.flags.c_contiguous:
        # In-place scatter needs the real buffer: reshape(-1) of a
        # non-contiguous view would silently mutate a copy.
        raise ValueError("pack_positions: words must be C-contiguous uint32")
    if len(positions):
        # The native scatter is unchecked C; validate here so corrupt input
        # raises instead of corrupting the heap.
        n_rows = words.size // words_per_row
        pos = np.asarray(positions, dtype=np.uint64)
        if int(pos.max()) >= n_rows * slice_width:
            raise ValueError("pack_positions: position out of range")
        if np.any((pos % np.uint64(slice_width)) >>
                  np.uint64(5) >= words_per_row):
            raise ValueError("pack_positions: column exceeds words_per_row")
    lib = _load()
    if lib is not None:
        positions = _contig(positions, np.uint64)
        flat = words.reshape(-1)    # alive until the call returns
        lib.pack_positions_u32(_ptr(positions), len(positions),
                               slice_width, words_per_row, _ptr(flat))
        return
    pos = positions.astype(np.uint64)
    rows = (pos // np.uint64(slice_width)).astype(np.int64)
    cols = pos % np.uint64(slice_width)
    flat = rows * words_per_row + (cols >> np.uint64(5)).astype(np.int64)
    np.bitwise_or.at(words.reshape(-1), flat,
                     (np.uint32(1) << (cols & np.uint64(31)).astype(np.uint32)))


def unpack_words(words: np.ndarray) -> np.ndarray:
    """Expand a u32 word vector into sorted u64 bit positions."""
    lib = _load()
    if lib is not None:
        words = _contig(words, np.uint32)
        total = int(np.bitwise_count(words).sum())
        out = np.empty(total, dtype=np.uint64)
        n = lib.unpack_words_u32(_ptr(words), len(words), _ptr(out))
        return out[:n]
    bits = ((words[:, None] >> np.arange(32, dtype=np.uint32)) &
            np.uint32(1)).astype(bool)
    w, b = np.nonzero(bits)
    return w.astype(np.uint64) * np.uint64(32) + b.astype(np.uint64)


def batch_add(keys, types, arr_ptrs, arr_ns, chunk_vals, chunk_starts,
              out_vals, out_offsets, out_ns, out_kind, out_bitmaps,
              out_bm_idx, changed, wal, wal_op_type: int) -> int:
    """One native crossing applying a whole add batch across touched
    containers (see bitops.cpp batch_add). Group types: 0=array,
    1=bitmap (mutated in place), 2=run (wire-form u16 buffer, decoded
    and merged through the array path — the engine's transparent run
    upgrade). Caller guarantees sizing and copy-on-write of in-place
    bitmap groups; raises if the native library is unavailable
    (roaring.apply_batch has the numpy fallback)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    return int(lib.batch_add(
        len(keys), _ptr(keys), _ptr(types), _ptr(arr_ptrs),
        _ptr(arr_ns), _ptr(chunk_vals), _ptr(chunk_starts),
        _ptr(out_vals), _ptr(out_offsets), _ptr(out_ns),
        _ptr(out_kind), _ptr(out_bitmaps), _ptr(out_bm_idx),
        _ptr(changed), _ptr(wal), wal_op_type))


def batch_remove(keys, types, arr_ptrs, arr_ns, chunk_vals, chunk_starts,
                 out_vals, out_offsets, out_ns, out_kind, changed, wal,
                 wal_op_type: int) -> int:
    """One native crossing applying a whole remove batch (bitops.cpp
    batch_remove)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    return int(lib.batch_remove(
        len(keys), _ptr(keys), _ptr(types), _ptr(arr_ptrs),
        _ptr(arr_ns), _ptr(chunk_vals), _ptr(chunk_starts),
        _ptr(out_vals), _ptr(out_offsets), _ptr(out_ns),
        _ptr(out_kind), _ptr(changed), _ptr(wal), wal_op_type))


def write_snapshot_fd(fd: int, keys, ns, types, ptrs) -> int:
    """Write a whole roaring snapshot from a serialization-table capture
    via batched writev straight out of the container buffers (bitops.cpp
    write_snapshot_fd). Returns bytes written, or -1 on IO error; raises
    if the native library is unavailable (write_frozen falls back)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    return int(lib.write_snapshot_fd(fd, len(keys), _ptr(keys),
                                     _ptr(ns), _ptr(types), _ptr(ptrs)))


def bitmap_intersection_count(keys_a, types_a, ptrs_a, ns_a,
                              keys_b, types_b, ptrs_b, ns_b) -> int:
    """Whole-bitmap intersection count over two container tables in ONE
    crossing (bitops.cpp bitmap_intersection_count); raises when the
    native library is unavailable — the caller keeps the per-container
    walk as the fallback."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    return int(lib.bitmap_intersection_count(
        len(keys_a), _ptr(keys_a), _ptr(types_a), _ptr(ptrs_a),
        _ptr(ns_a), len(keys_b), _ptr(keys_b), _ptr(types_b),
        _ptr(ptrs_b), _ptr(ns_b)))


def pack_slab(table: np.ndarray, pool: np.ndarray, out: np.ndarray,
              max_slots: int) -> int:
    """A residency fill's whole pack in ONE crossing (bitops.cpp
    pack_slab_u32). ``table`` is C-contiguous int64 ``[n, 4]``, a row a
    non-empty container: (slab slot, kind 0 / 1 / 2 = array / bitmap /
    run, buffer address, length in the buffer's own elements), or kind
    3, an array whose values begin at that element of ``pool`` (u32);
    the caller keeps the buffers alive until this returns. ``out`` is
    u32 ``[n_rows, 32768]``. Returns G > 0 where the gate (``max_slots``
    > 0) passed: ``out`` begins with the lanes, then the values, each
    ``[n_rows * 256, G]``; 0 where ``out`` is the dense block. Raises if
    the native library is unavailable (ops.packed keeps the numpy
    pass)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    if (out.dtype != _U32 or not out.flags.c_contiguous or out.ndim != 2
            or out.shape[1] != 32768):
        raise ValueError("pack_slab: out must be C-contiguous u32[n, 32768]")
    if table.dtype != np.int64 or table.shape[1:] != (4,) or \
            not table.flags.c_contiguous:
        raise ValueError("pack_slab: table must be C-contiguous int64[n, 4]")
    if pool.dtype != _U32 or not pool.flags.c_contiguous:
        raise ValueError("pack_slab: pool must be C-contiguous u32")
    return int(lib.pack_slab_u32(len(table), _ptr(table), out.shape[0],
                                 max_slots, _ptr(pool), _ptr(out)))


def bench_setbit(path: str, positions: np.ndarray,
                 max_op_n: int = 2000) -> int:
    """Run the native write-path micro-engine (mutate + WAL append +
    snapshot cadence) over u64 fragment positions; returns bits
    changed, or raises if the native library is unavailable. The
    measured host denominator for the SetBit path."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable")
    pos = _contig(positions, np.uint64)
    rc = lib.bench_setbit(path.encode(), _ptr(pos), len(pos),
                          max_op_n)
    if rc < 0:
        raise OSError("bench_setbit IO error")
    return rc


def parse_csv_pairs(data: bytes):
    """One-pass native parse of a ``digits,digits\\n`` byte buffer →
    (rows u64, cols u64), or None when the library is unavailable OR
    the buffer has any other shape (blank/3-field/non-digit lines,
    values past 2^64-1) — the caller's exact per-row path owns the
    error messages. Strictness matches the reference's ParseUint."""
    lib = _load()
    if lib is None or not data:
        return None
    cap = data.count(b"\n") + 1
    rows = np.empty(cap, dtype=np.uint64)
    cols = np.empty(cap, dtype=np.uint64)
    n = lib.parse_csv_u64_pairs(data, len(data), _ptr(rows),
                                _ptr(cols), cap)
    if n < 0:
        return None
    return rows[:n], cols[:n]


def available() -> bool:
    return _load() is not None
