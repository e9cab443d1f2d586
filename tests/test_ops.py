"""Device kernel layer tests: pack/unpack round-trips and parity between the
host roaring engine (semantics reference) and the XLA/Pallas kernels."""

import numpy as np
import pytest

from pilosa_tpu import SLICE_WIDTH
from pilosa_tpu.ops import kernels, packed
from pilosa_tpu.storage.roaring import Bitmap


def rand_bitmap(rng, n, hi):
    return Bitmap.from_sorted(
        rng.choice(hi, size=n, replace=False).astype(np.uint64))


class TestPacking:
    def test_pack_dense_container_is_view_equal(self):
        # A dense container must blit: positions 0..65535 → all-ones words.
        b = Bitmap.from_sorted(np.arange(1 << 16, dtype=np.uint64))
        words = packed.pack_bitmap(b, packed.WORDS_PER_SLICE)
        assert np.all(words[:2048] == 0xFFFFFFFF)
        assert np.all(words[2048:] == 0)

    def test_pack_unpack_roundtrip(self):
        rng = np.random.default_rng(1)
        b = rand_bitmap(rng, 10000, SLICE_WIDTH)
        words = packed.pack_bitmap(b, packed.WORDS_PER_SLICE)
        back = packed.unpack_to_bitmap(words)
        assert np.array_equal(back.values(), b.values())

    def test_pack_rows_layout(self):
        # storage positions pos = row*SLICE_WIDTH + col (fragment layout)
        storage = Bitmap(0, 31, 32, SLICE_WIDTH + 5, 3 * SLICE_WIDTH - 1)
        m = packed.pack_rows(storage, [0, 1, 2])
        assert m.shape == (3, packed.WORDS_PER_SLICE)
        assert m[0, 0] == (1 | (1 << 31))
        assert m[0, 1] == 1
        assert m[1, 0] == (1 << 5)
        assert m[2, -1] == (1 << 31)

    def test_pack_base_word_window(self):
        b = Bitmap(0, 100 * 32, 100 * 32 + 7)
        words = packed.pack_bitmap(b, 8, base_word=100)
        assert words[0] == (1 | (1 << 7))
        assert np.all(words[1:] == 0)


class TestKernelParity:
    @pytest.mark.parametrize("op,ref", [
        ("and", lambda a, b: a.intersect(b)),
        ("or", lambda a, b: a.union(b)),
        ("andnot", lambda a, b: a.difference(b)),
        ("xor", lambda a, b: a.xor(b)),
    ])
    def test_set_op_matches_roaring(self, op, ref):
        import jax

        from pilosa_tpu.parallel import mesh as mesh_mod
        rng = np.random.default_rng(kernels.OPS.index(op))
        a, b = (rand_bitmap(rng, 5000, SLICE_WIDTH) for _ in range(2))
        aw = packed.pack_bitmap(a, packed.WORDS_PER_SLICE)
        bw = packed.pack_bitmap(b, packed.WORDS_PER_SLICE)
        # The production materializing primitive: the expression
        # evaluator behind mesh.materialize_expr_sharded / count_expr.
        expr = (op, ("leaf", 0), ("leaf", 1))
        got = np.asarray(jax.jit(
            lambda leaves: mesh_mod._eval_expr(expr, leaves))(
                np.stack([aw, bw])))
        want = packed.pack_bitmap(ref(a, b), packed.WORDS_PER_SLICE)
        assert np.array_equal(got, want)
        # counts agree with the host engine too
        count = int(np.asarray(kernels.op_count_rows(op, aw, bw)))
        assert count == ref(a, b).count()

    def test_intersection_count_parity(self):
        rng = np.random.default_rng(9)
        a, b = (rand_bitmap(rng, 20000, SLICE_WIDTH) for _ in range(2))
        aw = packed.pack_bitmap(a, packed.WORDS_PER_SLICE)
        bw = packed.pack_bitmap(b, packed.WORDS_PER_SLICE)
        assert int(np.asarray(kernels.op_count_rows("and", aw, bw))) \
            == a.intersection_count(b)

    def test_row_block_and_topk(self):
        rng = np.random.default_rng(3)
        n_rows = 50
        storage = Bitmap.from_sorted(np.sort(rng.choice(
            n_rows * SLICE_WIDTH, size=100000, replace=False)
            .astype(np.uint64)))
        rows = packed.pack_rows(storage, range(n_rows))
        other = rand_bitmap(rng, 30000, SLICE_WIDTH)
        ow = packed.pack_bitmap(other, packed.WORDS_PER_SLICE)
        counts = np.asarray(kernels.row_block_op_count("and", rows, ow))
        # parity vs host roaring per row
        for r in range(0, n_rows, 7):
            row_bm = storage.offset_range(0, r * SLICE_WIDTH,
                                          (r + 1) * SLICE_WIDTH)
            assert counts[r] == row_bm.intersection_count(other)

    def test_popcount_rows(self):
        rng = np.random.default_rng(4)
        b = rand_bitmap(rng, 12345, SLICE_WIDTH)
        w = packed.pack_bitmap(b, packed.WORDS_PER_SLICE)
        assert int(np.asarray(kernels.popcount_rows(w))) == b.count()
        m = np.stack([w, np.zeros_like(w)])
        assert list(np.asarray(kernels.popcount_rows(m))) == [b.count(), 0]


class TestCountTotal:
    def test_no_int32_overflow(self):
        # >2^31 total bits must not wrap (code-review regression).
        a = np.full((70000 // 8, 8 * 1024), 0xFFFFFFFF, dtype=np.uint32)
        total = kernels.op_count_total("or", a, a)
        assert total == a.size * 32


class TestSparseWords:
    """Host-side sparse (word idx, word value) extraction — the upload
    payload of the device densify kernel (cold-path sparse uploads)."""

    def _storage(self):
        import numpy as np
        from pilosa_tpu import SLICE_WIDTH
        from pilosa_tpu.storage.roaring import Bitmap
        rng = np.random.default_rng(1)
        st = Bitmap()
        rows = rng.integers(0, 6, 30000).astype(np.uint64)
        cols = rng.integers(0, SLICE_WIDTH, 30000).astype(np.uint64)
        # row 0 also gets a dense run -> bitmap containers
        dense = np.sort(rng.choice(SLICE_WIDTH // 4, 150000,
                                   replace=False)).astype(np.uint64)
        st.add_many(np.unique(np.concatenate(
            [rows * SLICE_WIDTH + cols, dense])))
        return st

    @staticmethod
    def _rows(st, ids):
        from pilosa_tpu import SLICE_WIDTH
        return [st.offset_range(0, r * SLICE_WIDTH, (r + 1) * SLICE_WIDTH)
                for r in ids]

    def test_pack_slab_matches_dense_pack(self):
        """Rows 1-5 hold ~20 set words a 128-word group and row 0
        bitmap containers: over the gate, so the block comes back dense
        and equals the streaming leg's pack of the same rows."""
        from pilosa_tpu.ops import packed
        st = self._storage()
        ids = [0, 1, 2, 3, 4, 5]
        sparse, block, taken = packed.pack_slab(self._rows(st, ids))
        assert sparse is None and taken > len(ids)
        assert (block == packed.pack_rows(st, ids)).all()

    def test_pack_slab_then_densify_kernel(self):
        """Every 16th column of rows 1 and 5 (about two set words a
        group) ships sparse, and the kernel rebuilds the dense pack."""
        import numpy as np
        from pilosa_tpu import SLICE_WIDTH
        from pilosa_tpu.ops import packed
        from pilosa_tpu.ops.pallas_kernels import densify_pallas
        from pilosa_tpu.storage.roaring import Bitmap
        pos = self._storage().values()
        st = Bitmap()
        st.add_many(pos[(pos % SLICE_WIDTH) % 16 == 0])
        ids = [1, 5]
        dense = packed.pack_rows(st, ids)
        (lanes, vals), block, _ = packed.pack_slab(self._rows(st, ids))
        assert block is None and lanes.shape == vals.shape
        assert lanes.shape[:2] == (2, packed.WORDS_PER_SLICE // 128)
        assert 1 < lanes.shape[2] <= 32 and dense.any()
        got = np.asarray(densify_pallas(
            lanes, vals, packed.WORDS_PER_SLICE, True))
        assert (got == dense).all()

    def test_pack_slab_empty(self):
        from pilosa_tpu.ops import packed
        from pilosa_tpu.storage.roaring import Bitmap
        (lanes, vals), block, taken = packed.pack_slab([Bitmap()])
        assert block is None and taken == 0
        assert lanes.shape == (1, packed.WORDS_PER_SLICE // 128, 1)
        assert not lanes.any() and not vals.any()
