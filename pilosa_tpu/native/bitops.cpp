// Host-side native bit kernels for pilosa_tpu.
//
// The reference's only native component is roaring/assembly_amd64.s — POPCNT
// loops fused with AND/OR/XOR/ANDNOT over u64 slices, plus sorted-array set
// ops in Go. On TPU the hot path moves to XLA/Pallas (pilosa_tpu/ops/); this
// library is the CPU-side equivalent for storage maintenance, import packing,
// and the no-TPU fallback, so none of those paths are Python-loop-bound.
//
// Built as a plain shared library (extern "C"), loaded via ctypes
// (pilosa_tpu/storage/native.py). g++ -O3 -march=native autovectorizes the
// popcount loops with __builtin_popcountll.

#include <cstdint>
#include <cstdlib>
#include <cstring>

extern "C" {

// ---- fused popcount + bitwise op over u64 words ----------------------------

uint64_t popcnt_and(const uint64_t* a, const uint64_t* b, int64_t n) {
    uint64_t total = 0;
    for (int64_t i = 0; i < n; i++) total += __builtin_popcountll(a[i] & b[i]);
    return total;
}

uint64_t popcnt_or(const uint64_t* a, const uint64_t* b, int64_t n) {
    uint64_t total = 0;
    for (int64_t i = 0; i < n; i++) total += __builtin_popcountll(a[i] | b[i]);
    return total;
}

uint64_t popcnt_xor(const uint64_t* a, const uint64_t* b, int64_t n) {
    uint64_t total = 0;
    for (int64_t i = 0; i < n; i++) total += __builtin_popcountll(a[i] ^ b[i]);
    return total;
}

uint64_t popcnt_andnot(const uint64_t* a, const uint64_t* b, int64_t n) {
    uint64_t total = 0;
    for (int64_t i = 0; i < n; i++) total += __builtin_popcountll(a[i] & ~b[i]);
    return total;
}

uint64_t popcnt(const uint64_t* a, int64_t n) {
    uint64_t total = 0;
    for (int64_t i = 0; i < n; i++) total += __builtin_popcountll(a[i]);
    return total;
}

// ---- sorted u32 array set ops ----------------------------------------------
// Standard two-pointer merges; out must have room for the worst case
// (min(na,nb) for intersect, na+nb for union, na for difference).

int64_t intersect_sorted_u32(const uint32_t* a, int64_t na,
                             const uint32_t* b, int64_t nb, uint32_t* out) {
    int64_t i = 0, j = 0, k = 0;
    while (i < na && j < nb) {
        if (a[i] < b[j]) i++;
        else if (a[i] > b[j]) j++;
        else { out[k++] = a[i]; i++; j++; }
    }
    return k;
}

int64_t intersection_count_sorted_u32(const uint32_t* a, int64_t na,
                                      const uint32_t* b, int64_t nb) {
    int64_t i = 0, j = 0, k = 0;
    while (i < na && j < nb) {
        if (a[i] < b[j]) i++;
        else if (a[i] > b[j]) j++;
        else { k++; i++; j++; }
    }
    return k;
}

int64_t union_sorted_u32(const uint32_t* a, int64_t na,
                         const uint32_t* b, int64_t nb, uint32_t* out) {
    int64_t i = 0, j = 0, k = 0;
    while (i < na && j < nb) {
        if (a[i] < b[j]) out[k++] = a[i++];
        else if (a[i] > b[j]) out[k++] = b[j++];
        else { out[k++] = a[i]; i++; j++; }
    }
    while (i < na) out[k++] = a[i++];
    while (j < nb) out[k++] = b[j++];
    return k;
}

int64_t difference_sorted_u32(const uint32_t* a, int64_t na,
                              const uint32_t* b, int64_t nb, uint32_t* out) {
    int64_t i = 0, j = 0, k = 0;
    while (i < na && j < nb) {
        if (a[i] < b[j]) out[k++] = a[i++];
        else if (a[i] > b[j]) j++;
        else { i++; j++; }
    }
    while (i < na) out[k++] = a[i++];
    return k;
}

// ---- packing: u64 bit positions -> dense u32 word matrix -------------------
// Scatter set-bit positions into a row-major uint32 word buffer of
// words_per_row words per row: pos -> words[row * words_per_row + col/32].
// Positions are fragment-local: pos = row * slice_width + col.

void pack_positions_u32(const uint64_t* positions, int64_t n,
                        uint64_t slice_width, int64_t words_per_row,
                        uint32_t* words) {
    for (int64_t i = 0; i < n; i++) {
        uint64_t pos = positions[i];
        uint64_t row = pos / slice_width;
        uint64_t col = pos % slice_width;
        words[row * words_per_row + (col >> 5)] |= (1u << (col & 31));
    }
}

// Unpack one row of u32 words into sorted column ids; returns count.
int64_t unpack_words_u32(const uint32_t* words, int64_t n_words,
                         uint64_t* out) {
    int64_t k = 0;
    for (int64_t i = 0; i < n_words; i++) {
        uint32_t w = words[i];
        while (w) {
            int bit = __builtin_ctz(w);
            out[k++] = (uint64_t)i * 32 + bit;
            w &= w - 1;
        }
    }
    return k;
}

// ---- whole-bitmap intersection count ---------------------------------------
// One crossing for an entire two-level intersection count: zip both
// bitmaps' container tables (sorted keys + per-container type/ptr/n)
// and dispatch per pair kind — the reference's intersectionCount
// container dispatch (roaring.go:1192-1268) with the Python walk
// removed. Tables are the serialization tables the batch engine
// already maintains (roaring._SerTable).

extern "C" int64_t bitmap_intersection_count(
        int64_t na, const uint64_t* keys_a, const uint8_t* types_a,
        const uint64_t* ptrs_a, const int64_t* ns_a,
        int64_t nb, const uint64_t* keys_b, const uint8_t* types_b,
        const uint64_t* ptrs_b, const int64_t* ns_b) {
    int64_t i = 0, j = 0;
    int64_t total = 0;
    while (i < na && j < nb) {
        if (keys_a[i] < keys_b[j]) { i++; continue; }
        if (keys_a[i] > keys_b[j]) { j++; continue; }
        if (ns_a[i] && ns_b[j]) {
            bool bm_a = types_a[i] != 0, bm_b = types_b[j] != 0;
            if (!bm_a && !bm_b) {
                total += intersection_count_sorted_u32(
                    (const uint32_t*)ptrs_a[i], ns_a[i],
                    (const uint32_t*)ptrs_b[j], ns_b[j]);
            } else if (bm_a && bm_b) {
                total += (int64_t)popcnt_and(
                    (const uint64_t*)ptrs_a[i],
                    (const uint64_t*)ptrs_b[j], 1024);
            } else {
                const uint32_t* arr = (const uint32_t*)(
                    bm_a ? ptrs_b[j] : ptrs_a[i]);
                int64_t n_arr = bm_a ? ns_b[j] : ns_a[i];
                const uint64_t* bm = (const uint64_t*)(
                    bm_a ? ptrs_a[i] : ptrs_b[j]);
                for (int64_t t = 0; t < n_arr; t++) {
                    uint32_t v = arr[t];
                    total += (bm[v >> 6] >> (v & 63)) & 1ULL;
                }
            }
        }
        i++;
        j++;
    }
    return total;
}

// ---- batched write engine ---------------------------------------------------
// ONE crossing per mutation batch: container merges, changed-value
// detection, and WAL record construction all happen here, so the serving
// write path runs at compiled speed with ctypes overhead amortized over
// the whole batch (per-op ctypes was measured a loss; see
// storage/native.py). The reference's equivalent per-op loop is
// fragment.go:369-459; this is its batch-grouped native form.
//
// Group layout (caller = roaring.Bitmap.apply_batch): one group per
// touched container, in key order. types[g]: 0 = array container
// (sorted u32 values at arr_ptrs[g], count arr_ns[g]); 1 = bitmap
// container (u64[1024] at arr_ptrs[g], mutated IN PLACE — caller
// guarantees copy-on-write happened); 2 = run container (wire-form
// u16 buffer [numRuns, start, len-1, ...] at arr_ptrs[g], cardinality
// arr_ns[g]) — decoded to sorted values here and merged through the
// array path, i.e. the engine transparently upgrades runs (output is
// array or bitmap; roaring.Bitmap.optimize() re-compresses later).
// chunk values are sorted, unique, < 65536.
//
// Outputs per group:
//   out_kind[g]: 0 = merged array written at out_vals[out_offsets[g]]
//                1 = converted to bitmap at out_bitmaps[out_bm_idx[g]*1024]
//                2 = existing bitmap mutated in place
//   out_ns[g]:   new container cardinality
// Changed (newly set / newly cleared) global positions (keys[g]<<16 | v)
// are appended to `changed`; when wal_op_type >= 0 a 13-byte WAL record
// (type, u64 LE value, FNV-1a32 of the first 9 bytes) per changed value
// is appended to `wal`. Returns total changed count.

namespace {

const int64_t kWordsPerContainer = 1024;  // u64 words per bitmap container

inline void wal_record(uint8_t* rec, uint8_t typ, uint64_t pos) {
    rec[0] = typ;
    memcpy(rec + 1, &pos, 8);
    uint32_t h = 2166136261u;
    for (int i = 0; i < 9; i++) h = (h ^ rec[i]) * 16777619u;
    memcpy(rec + 9, &h, 4);
}

// Expand a wire-form run buffer into sorted u32 values; returns count.
int64_t decode_runs_u32(const uint16_t* runs, uint32_t* out) {
    int64_t n_runs = runs[0];
    int64_t k = 0;
    for (int64_t i = 0; i < n_runs; i++) {
        uint32_t start = runs[1 + 2 * i];
        uint32_t len = (uint32_t)runs[2 + 2 * i] + 1;
        for (uint32_t v = 0; v < len; v++) out[k++] = start + v;
    }
    return k;
}

}  // namespace

extern "C" int64_t batch_add(
        int64_t n_groups, const uint64_t* keys, const uint8_t* types,
        const uint64_t* arr_ptrs, const int64_t* arr_ns,
        const uint32_t* chunk_vals, const int64_t* chunk_starts,
        uint32_t* out_vals, int64_t* out_offsets, int64_t* out_ns,
        uint8_t* out_kind, uint64_t* out_bitmaps, int64_t* out_bm_idx,
        uint64_t* changed, uint8_t* wal, int64_t wal_op_type) {
    int64_t n_changed = 0, out_off = 0, bm_count = 0;
    for (int64_t g = 0; g < n_groups; g++) {
        const uint32_t* b = chunk_vals + chunk_starts[g];
        int64_t nb = chunk_starts[g + 1] - chunk_starts[g];
        uint64_t base = keys[g] << 16;
        int64_t before_changed = n_changed;
        if (types[g] == 1) {  // bitmap container, in-place
            uint64_t* bm = (uint64_t*)arr_ptrs[g];
            int64_t n = arr_ns[g];
            for (int64_t i = 0; i < nb; i++) {
                uint32_t v = b[i];
                uint64_t bit = 1ULL << (v & 63);
                if (bm[v >> 6] & bit) continue;
                bm[v >> 6] |= bit;
                n++;
                changed[n_changed++] = base | v;
            }
            out_kind[g] = 2;
            out_ns[g] = n;
            out_bm_idx[g] = -1;
            out_offsets[g] = -1;
        } else {  // array/run container: two-pointer union into out_vals
            const uint32_t* a = (const uint32_t*)arr_ptrs[g];
            int64_t na = arr_ns[g];
            uint32_t* decoded = nullptr;
            if (types[g] == 2) {  // run: decode, then merge as array
                decoded = (uint32_t*)malloc((na ? na : 1) * 4);
                na = decode_runs_u32((const uint16_t*)arr_ptrs[g],
                                     decoded);
                a = decoded;
            }
            uint32_t* out = out_vals + out_off;
            int64_t i = 0, j = 0, k = 0;
            while (i < na && j < nb) {
                if (a[i] < b[j]) out[k++] = a[i++];
                else if (a[i] > b[j]) out[k++] = b[j++];
                else { out[k++] = a[i]; i++; j++; }
            }
            while (i < na) out[k++] = a[i++];
            while (j < nb) out[k++] = b[j++];
            // changed = chunk values not present in the existing array
            // (second pass keeps the union loop branch-light).
            i = 0; j = 0;
            while (j < nb) {
                while (i < na && a[i] < b[j]) i++;
                if (i >= na || a[i] != b[j]) changed[n_changed++] = base | b[j];
                j++;
            }
            if (k > 4096) {  // convert to bitmap container
                uint64_t* bm = out_bitmaps + bm_count * kWordsPerContainer;
                memset(bm, 0, kWordsPerContainer * 8);
                for (int64_t t = 0; t < k; t++)
                    bm[out[t] >> 6] |= 1ULL << (out[t] & 63);
                out_kind[g] = 1;
                out_bm_idx[g] = bm_count++;
                out_offsets[g] = -1;
            } else {
                out_kind[g] = 0;
                out_offsets[g] = out_off;
                out_bm_idx[g] = -1;
                out_off += k;
            }
            out_ns[g] = k;
            free(decoded);
        }
        if (wal_op_type >= 0) {
            for (int64_t t = before_changed; t < n_changed; t++)
                wal_record(wal + t * 13, (uint8_t)wal_op_type, changed[t]);
        }
    }
    return n_changed;
}

// Batched remove. Same group layout as batch_add (run groups decode and
// go through the array path). Array groups write the difference to
// out_vals (kind 0). Bitmap groups clear in place; if the result drops
// to <=4096 values it is UNPACKED to an array in out_vals (kind 0) to
// restore the serialization invariant, else kind 2.
extern "C" int64_t batch_remove(
        int64_t n_groups, const uint64_t* keys, const uint8_t* types,
        const uint64_t* arr_ptrs, const int64_t* arr_ns,
        const uint32_t* chunk_vals, const int64_t* chunk_starts,
        uint32_t* out_vals, int64_t* out_offsets, int64_t* out_ns,
        uint8_t* out_kind, uint64_t* changed, uint8_t* wal,
        int64_t wal_op_type) {
    int64_t n_changed = 0, out_off = 0;
    for (int64_t g = 0; g < n_groups; g++) {
        const uint32_t* b = chunk_vals + chunk_starts[g];
        int64_t nb = chunk_starts[g + 1] - chunk_starts[g];
        uint64_t base = keys[g] << 16;
        int64_t before_changed = n_changed;
        if (types[g] == 1) {
            uint64_t* bm = (uint64_t*)arr_ptrs[g];
            int64_t n = arr_ns[g];
            for (int64_t i = 0; i < nb; i++) {
                uint32_t v = b[i];
                uint64_t bit = 1ULL << (v & 63);
                if (!(bm[v >> 6] & bit)) continue;
                bm[v >> 6] &= ~bit;
                n--;
                changed[n_changed++] = base | v;
            }
            if (n <= 4096) {  // unpack to array (serialization invariant)
                uint32_t* out = out_vals + out_off;
                int64_t k = 0;
                for (int64_t w = 0; w < kWordsPerContainer; w++) {
                    uint64_t word = bm[w];
                    while (word) {
                        int bit = __builtin_ctzll(word);
                        out[k++] = (uint32_t)(w * 64 + bit);
                        word &= word - 1;
                    }
                }
                out_kind[g] = 0;
                out_offsets[g] = out_off;
                out_off += k;
            } else {
                out_kind[g] = 2;
                out_offsets[g] = -1;
            }
            out_ns[g] = n;
        } else {
            const uint32_t* a = (const uint32_t*)arr_ptrs[g];
            int64_t na = arr_ns[g];
            uint32_t* decoded = nullptr;
            if (types[g] == 2) {
                decoded = (uint32_t*)malloc((na ? na : 1) * 4);
                na = decode_runs_u32((const uint16_t*)arr_ptrs[g],
                                     decoded);
                a = decoded;
            }
            uint32_t* out = out_vals + out_off;
            int64_t i = 0, j = 0, k = 0;
            while (i < na) {
                while (j < nb && b[j] < a[i]) j++;
                if (j < nb && b[j] == a[i]) {
                    changed[n_changed++] = base | a[i];
                    i++;
                } else {
                    out[k++] = a[i++];
                }
            }
            out_kind[g] = 0;
            out_offsets[g] = out_off;
            out_ns[g] = k;
            out_off += k;
            free(decoded);
        }
        if (wal_op_type >= 0) {
            for (int64_t t = before_changed; t < n_changed; t++)
                wal_record(wal + t * 13, (uint8_t)wal_op_type, changed[t]);
        }
    }
    return n_changed;
}

// ---- residency fill: one slab's containers -> its upload payload -----------
// The host half of a residency fill (ops/packed.py pack_slab) in ONE
// crossing, so a fill lets go of the interpreter once. The slab is
// n_rows slice-rows of 32768 u32 words, 16 container slots of 2048 words
// each; `table` holds n_cont rows of four int64s, one a non-empty
// container: (slot = slice-row * 16 + key, kind, buffer address, length)
// with kind 0 = sorted u32 array (length = values), 1 = u64 bitmap
// (length = words, 1024), 2 = wire-form u16 run buffer (length = u16s),
// 3 = sorted u32 array whose values begin at element `address` of
// `pool` (small arrays come concatenated, so that the caller takes no
// address one by one). Nothing is read past a buffer's length or
// written past `out`.
//
// The gate (max_slots > 0): G = the most set words in any 128-word group
// of any slice-row, rounded up to a power of two. G <= max_slots writes
// the bucketed payload the device densify kernel takes into the front of
// `out` — lanes [n_rows * 256, G], then values of the same shape: a
// group's set words in ascending lane order, value 0 = padding — and
// returns G. Otherwise (or max_slots = 0) `out` becomes the dense block
// [n_rows, 32768] and the call returns 0. `out` holds n_rows * 32768 u32
// words, which both layouts fit (2 * 256 * G <= 32768 while G <= 64).

namespace {

const int64_t kSlotWords = 2048;                     // u32 words a slot
const int64_t kLanes = 128;                          // words a group
const int64_t kSlotGroups = kSlotWords / kLanes;     // 16 groups a slot

// Set bits [lo, hi) of a slot's words.
void set_bit_range(uint32_t* w, uint32_t lo, uint32_t hi) {
    if (lo >= hi) return;
    uint32_t first = lo >> 5, last = (hi - 1) >> 5;
    uint32_t head = ~0u << (lo & 31), tail = ~0u >> (31 - ((hi - 1) & 31));
    if (first == last) { w[first] |= head & tail; return; }
    w[first] |= head;
    for (uint32_t i = first + 1; i < last; i++) w[i] = ~0u;
    w[last] |= tail;
}

// A bitmap or run container's words into `w` (2048 u32, zeroed).
void slot_words(int64_t kind, const void* buf, int64_t len, uint32_t* w) {
    if (kind == 1) {
        memcpy(w, buf, (len < kWordsPerContainer ? len : kWordsPerContainer)
                       * 8);
        return;
    }
    const uint16_t* runs = (const uint16_t*)buf;
    if (len < 1) return;
    int64_t n_runs = runs[0];
    if (n_runs > (len - 1) / 2) n_runs = (len - 1) / 2;
    for (int64_t i = 0; i < n_runs; i++) {
        uint32_t lo = runs[1 + 2 * i];
        uint32_t hi = lo + (uint32_t)runs[2 + 2 * i] + 1;
        set_bit_range(w, lo, hi < 65536 ? hi : 65536);
    }
}

// The most set words in any group of a sorted array container.
int64_t array_fullest(const uint32_t* a, int64_t n) {
    int64_t fullest = 0, count = 0;
    uint32_t group = ~0u, word = ~0u;
    for (int64_t i = 0; i < n; i++) {
        uint32_t v = a[i];
        if (v >> 12 != group) { group = v >> 12; count = 0; }
        if (v >> 5 != word) {
            word = v >> 5;
            if (++count > fullest) fullest = count;
        }
    }
    return fullest;
}

int64_t words_fullest(const uint32_t* w) {
    int64_t fullest = 0;
    for (int64_t g = 0; g < kSlotGroups; g++) {
        int64_t count = 0;
        for (int64_t i = 0; i < kLanes; i++) count += w[g * kLanes + i] != 0;
        if (count > fullest) fullest = count;
    }
    return fullest;
}

// A table row's kind (a pooled array is an array) and its buffer.
int64_t entry_kind(const int64_t* e) { return e[1] == 3 ? 0 : e[1]; }

const void* entry_buf(const int64_t* e, const uint32_t* pool) {
    return e[1] == 3 ? (const void*)(pool + e[2]) : (const void*)e[2];
}

}  // namespace

int64_t pack_slab_u32(int64_t n_cont, const int64_t* table, int64_t n_rows,
                      int64_t max_slots, const uint32_t* pool,
                      uint32_t* out) {
    const int64_t n_slots = n_rows * (32768 / kSlotWords);
    uint32_t scratch[kSlotWords];
    int64_t g_pad = 0;
    if (max_slots > 64) max_slots = 64;
    if (max_slots > 0) {
        int64_t fullest = 1;
        for (int64_t c = 0; c < n_cont && fullest <= max_slots; c++) {
            const int64_t* e = table + 4 * c;
            if (e[0] < 0 || e[0] >= n_slots) continue;
            int64_t f, kind = entry_kind(e);
            const void* buf = entry_buf(e, pool);
            if (kind == 0) {
                f = array_fullest((const uint32_t*)buf, e[3]);
            } else {
                memset(scratch, 0, sizeof scratch);
                slot_words(kind, buf, e[3], scratch);
                f = words_fullest(scratch);
            }
            if (f > fullest) fullest = f;
        }
        g_pad = 1;
        while (g_pad < fullest) g_pad <<= 1;
        if (g_pad > max_slots) g_pad = 0;
    }
    if (g_pad == 0) {
        memset(out, 0, n_rows * 32768 * 4);
        for (int64_t c = 0; c < n_cont; c++) {
            const int64_t* e = table + 4 * c;
            if (e[0] < 0 || e[0] >= n_slots) continue;
            uint32_t* w = out + e[0] * kSlotWords;
            int64_t kind = entry_kind(e);
            if (kind != 0) {
                slot_words(kind, entry_buf(e, pool), e[3], w);
                continue;
            }
            const uint32_t* a = (const uint32_t*)entry_buf(e, pool);
            for (int64_t i = 0; i < e[3]; i++) {
                uint32_t v = a[i];
                if (v < 65536) w[v >> 5] |= 1u << (v & 31);
            }
        }
        return 0;
    }
    const int64_t n_groups = n_slots * kSlotGroups;
    uint32_t* lanes = out;
    uint32_t* vals = out + n_groups * g_pad;
    memset(out, 0, 2 * n_groups * g_pad * 4);
    for (int64_t c = 0; c < n_cont; c++) {
        const int64_t* e = table + 4 * c;
        if (e[0] < 0 || e[0] >= n_slots) continue;
        int64_t group0 = e[0] * kSlotGroups, kind = entry_kind(e);
        if (kind == 0) {
            // Sorted values: each set word is complete when the next
            // value leaves it, and comes in ascending lane order.
            const uint32_t* a = (const uint32_t*)entry_buf(e, pool);
            int64_t k = 0;
            uint32_t group = ~0u, word = ~0u, bits = 0;
            for (int64_t i = 0; i <= e[3]; i++) {
                uint32_t v = i < e[3] ? a[i] : ~0u;
                if (v >> 5 == word) { bits |= 1u << (v & 31); continue; }
                if (bits && k < g_pad) {
                    int64_t at = (group0 + group) * g_pad + k++;
                    lanes[at] = word & (kLanes - 1);
                    vals[at] = bits;
                }
                if (v >= 65536) break;
                if (v >> 12 != group) { group = v >> 12; k = 0; }
                word = v >> 5;
                bits = 1u << (v & 31);
            }
            continue;
        }
        memset(scratch, 0, sizeof scratch);
        slot_words(kind, entry_buf(e, pool), e[3], scratch);
        for (int64_t g = 0; g < kSlotGroups; g++) {
            int64_t base = (group0 + g) * g_pad, k = 0;
            for (int64_t i = 0; i < kLanes && k < g_pad; i++) {
                uint32_t v = scratch[g * kLanes + i];
                if (v) { lanes[base + k] = (uint32_t)i; vals[base + k++] = v; }
            }
        }
    }
    return g_pad;
}

}  // extern "C"

// ---- native snapshot writer -------------------------------------------------
// Serializes a whole roaring snapshot (cookie/keyN/headers/offsets/container
// blocks — the reference format, roaring.go:475-533) straight from a table of
// container buffer pointers, using writev batches that point INTO the
// container buffers (zero copy, no GIL held during the call). The table is
// maintained incrementally by the batched write path, so the MAX_OP_N
// snapshot cadence stops costing O(all containers) of Python per rewrite.

#include <cstdlib>
#include <sys/uio.h>
#include <unistd.h>

namespace {

bool writev_full(int fd, struct iovec* iov, int n) {
    while (n > 0) {
        ssize_t w = writev(fd, iov, n);
        if (w < 0) return false;
        while (n > 0 && (size_t)w >= iov[0].iov_len) {
            w -= iov[0].iov_len;
            iov++;
            n--;
        }
        if (n > 0) {  // partial iovec
            iov[0].iov_base = (uint8_t*)iov[0].iov_base + w;
            iov[0].iov_len -= w;
        }
    }
    return true;
}

}  // namespace

extern "C" int64_t write_snapshot_fd(
        int fd, int64_t n_cont, const uint64_t* keys, const int64_t* ns,
        const uint8_t* types, const uint64_t* ptrs) {
    int64_t live = 0, body = 0;
    for (int64_t i = 0; i < n_cont; i++) {
        if (ns[i] == 0) continue;
        live++;
        body += types[i] ? kWordsPerContainer * 8 : ns[i] * 4;
    }
    int64_t head_len = 8 + live * 12 + live * 4;
    uint8_t* head = (uint8_t*)malloc(head_len ? head_len : 1);
    if (!head) return -1;
    uint32_t cookie = 12346, nl = (uint32_t)live;
    memcpy(head, &cookie, 4);
    memcpy(head + 4, &nl, 4);
    uint8_t* hp = head + 8;
    uint32_t* offp = (uint32_t*)(head + 8 + live * 12);
    uint32_t off = (uint32_t)head_len;
    for (int64_t i = 0; i < n_cont; i++) {
        if (ns[i] == 0) continue;
        memcpy(hp, &keys[i], 8);
        uint32_t nm1 = (uint32_t)(ns[i] - 1);
        memcpy(hp + 8, &nm1, 4);
        hp += 12;
        *offp++ = off;
        off += types[i] ? kWordsPerContainer * 8 : (uint32_t)(ns[i] * 4);
    }
    struct iovec hv = {head, (size_t)head_len};
    if (!writev_full(fd, &hv, 1)) { free(head); return -1; }
    free(head);
    // Container blocks via writev, IOV_MAX-sized batches, zero copy.
    const int kBatch = 1024;
    struct iovec iov[kBatch];
    int in = 0;
    for (int64_t i = 0; i < n_cont; i++) {
        if (ns[i] == 0) continue;
        iov[in].iov_base = (void*)ptrs[i];
        iov[in].iov_len = types[i] ? kWordsPerContainer * 8 : ns[i] * 4;
        if (++in == kBatch) {
            if (!writev_full(fd, iov, in)) return -1;
            in = 0;
        }
    }
    if (in && !writev_full(fd, iov, in)) return -1;
    return head_len + body;
}

// ---- native write-path micro-engine ----------------------------------------
// The measured host denominator for the SetBit path (the reference's is
// fragment.go:369-459 driven by ctl/bench.go:71-102; no Go toolchain in
// this image, so this is the C++ stand-in, as popcnt_and is for reads).
// Faithful shape: per op — locate the container (pos>>16), sorted-array
// insert or bitmap set with array->bitmap conversion at 4096, append a
// 13-byte op record to the data file with one unbuffered write(), and
// after every max_op_n ops rewrite a snapshot of all containers to a
// temp file, fsync, and rename over the data file (the same durability
// cadence the Python fragment and the reference both pay).

#include <cstdio>
#include <cstdlib>
#include <fcntl.h>
#include <unistd.h>

namespace {

struct WContainer {
    uint16_t* array;     // sorted u16 values, or null when bitmap
    uint64_t* bitmap;    // u64[1024], or null when array
    int32_t n;
    int32_t cap;
};

const int32_t kArrayMax = 4096;
const int32_t kBitmapWords = 1024;

bool wcontainer_add(WContainer* c, uint16_t v) {
    if (c->bitmap) {
        uint64_t bit = 1ULL << (v & 63);
        if (c->bitmap[v >> 6] & bit) return false;
        c->bitmap[v >> 6] |= bit;
        c->n++;
        return true;
    }
    // binary search
    int32_t lo = 0, hi = c->n;
    while (lo < hi) {
        int32_t mid = (lo + hi) / 2;
        if (c->array[mid] < v) lo = mid + 1; else hi = mid;
    }
    if (lo < c->n && c->array[lo] == v) return false;
    if (c->n + 1 > kArrayMax) {  // convert then set
        uint64_t* bm = (uint64_t*)calloc(kBitmapWords, 8);
        for (int32_t i = 0; i < c->n; i++)
            bm[c->array[i] >> 6] |= 1ULL << (c->array[i] & 63);
        free(c->array);
        c->array = nullptr;
        c->bitmap = bm;
        return wcontainer_add(c, v);
    }
    if (c->n == c->cap) {
        c->cap = c->cap ? c->cap * 2 : 8;
        c->array = (uint16_t*)realloc(c->array, c->cap * 2);
    }
    memmove(c->array + lo + 1, c->array + lo, (c->n - lo) * 2);
    c->array[lo] = v;
    c->n++;
    return true;
}

}  // namespace

// Runs n_ops SetBit ops (64-bit fragment positions) against a data file
// at `path` with WAL append per op and a snapshot rewrite every
// max_op_n ops. Returns ops actually changed (idempotent re-sets don't
// append), or -1 on IO error. Elapsed time is the caller's job.
extern "C" int64_t bench_setbit(const char* path, const uint64_t* positions,
                     int64_t n_ops, int64_t max_op_n) {
    int64_t max_key = 0;
    for (int64_t i = 0; i < n_ops; i++)
        if ((int64_t)(positions[i] >> 16) > max_key)
            max_key = positions[i] >> 16;
    WContainer* conts = (WContainer*)calloc(max_key + 1,
                                            sizeof(WContainer));
    int fd = open(path, O_WRONLY | O_CREAT | O_TRUNC, 0644);
    if (fd < 0) { free(conts); return -1; }

    unsigned char rec[13];
    int64_t changed = 0, op_n = 0;
    char tmp_path[4096];
    snprintf(tmp_path, sizeof tmp_path, "%s.snapshotting", path);

    for (int64_t i = 0; i < n_ops; i++) {
        uint64_t pos = positions[i];
        WContainer* c = &conts[pos >> 16];
        if (!wcontainer_add(c, (uint16_t)(pos & 0xFFFF))) continue;
        changed++;
        // 13-byte op record: type(1) + value(8) + checksum(4) — the
        // same record size the storage WAL appends per mutation.
        rec[0] = 0;
        memcpy(rec + 1, &pos, 8);
        uint32_t sum = (uint32_t)(pos ^ (pos >> 32)) * 2654435761u;
        memcpy(rec + 9, &sum, 4);
        if (write(fd, rec, 13) != 13) { close(fd); free(conts); return -1; }
        if (++op_n > max_op_n) {
            // snapshot: rewrite every live container, fsync, rename.
            int sfd = open(tmp_path, O_WRONLY | O_CREAT | O_TRUNC, 0644);
            if (sfd < 0) { close(fd); free(conts); return -1; }
            for (int64_t k = 0; k <= max_key; k++) {
                WContainer* cc = &conts[k];
                if (cc->n == 0) continue;
                if (cc->bitmap) {
                    if (write(sfd, cc->bitmap, kBitmapWords * 8) < 0)
                        { close(sfd); close(fd); free(conts); return -1; }
                } else {
                    if (write(sfd, cc->array, cc->n * 2) < 0)
                        { close(sfd); close(fd); free(conts); return -1; }
                }
            }
            fsync(sfd);
            close(sfd);
            if (rename(tmp_path, path) != 0)
                { close(fd); free(conts); return -1; }
            close(fd);
            fd = open(path, O_WRONLY | O_APPEND, 0644);
            if (fd < 0) { free(conts); return -1; }
            op_n = 0;
        }
    }
    close(fd);
    for (int64_t k = 0; k <= max_key; k++) {
        free(conts[k].array);
        free(conts[k].bitmap);
    }
    free(conts);
    return changed;
}

// Parse a "digits,digits\n"* byte buffer into u64 row/col arrays in one
// pass (the CSV import fast lane; ~6x numpy's general text parser).
// Strict: exactly two fields per line, CRLF tolerated, any other shape
// (blank line, third field, non-digit, value past 2^64-1) returns -1
// and the caller falls back to the exact per-row Python path that owns
// the error messages. Returns the number of parsed pairs.
extern "C" int64_t parse_csv_u64_pairs(
        const uint8_t* buf, int64_t n, uint64_t* rows, uint64_t* cols,
        int64_t max_pairs) {
    int64_t out = 0;
    int64_t i = 0;
    while (i < n) {
        if (out >= max_pairs) return -1;
        for (int field = 0; field < 2; field++) {
            if (i >= n || buf[i] < '0' || buf[i] > '9') return -1;
            unsigned __int128 v = 0;
            int digits = 0;
            while (i < n && buf[i] >= '0' && buf[i] <= '9') {
                v = v * 10 + (uint64_t)(buf[i] - '0');
                if (++digits > 20) return -1;
                i++;
            }
            if (v > (unsigned __int128)UINT64_MAX) return -1;
            if (field == 0) {
                if (i >= n || buf[i] != ',') return -1;
                i++;
                rows[out] = (uint64_t)v;
            } else {
                cols[out] = (uint64_t)v;
            }
        }
        out++;
        if (i < n) {
            if (buf[i] == '\r') i++;
            if (i >= n || buf[i] != '\n') return -1;
            i++;
        }
    }
    return out;
}
