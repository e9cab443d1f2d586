"""Budgeted HBM residency: the process-wide device block cache.

SURVEY §7 hard part 2: 50k cached rows × many fragments exceed HBM, so
device blocks live in one budgeted LRU (parallel.residency) keyed by
fragment (uid, generation) — repeat queries reuse uploads, writes
invalidate by key, the byte budget bounds total HBM.
"""

import numpy as np
import pytest

from pilosa_tpu.parallel import residency
from pilosa_tpu.parallel.residency import DeviceBlockCache


def _arr(n_bytes: int):
    import jax
    return jax.device_put(np.zeros(n_bytes // 4, dtype=np.uint32))


class TestDeviceBlockCache:
    def test_hit_returns_same_array(self):
        c = DeviceBlockCache(budget_bytes=1 << 20)
        a = c.get_or_build(("k",), lambda: _arr(1024))
        b = c.get_or_build(("k",), lambda: pytest.fail("rebuilt on hit"))
        assert a is b
        assert c.hits == 1 and c.misses == 1

    def test_budget_evicts_lru(self):
        c = DeviceBlockCache(budget_bytes=4096)
        c.get_or_build(("a",), lambda: _arr(2048))
        c.get_or_build(("b",), lambda: _arr(2048))
        c.get_or_build(("a",), lambda: pytest.fail("a evicted early"))
        c.get_or_build(("c",), lambda: _arr(2048))  # evicts b (LRU)
        assert c.evictions == 1
        assert c.used_bytes <= 4096
        rebuilt = []
        c.get_or_build(("b",), lambda: rebuilt.append(1) or _arr(2048))
        assert rebuilt  # b was the evicted one

    def test_oversize_entry_not_cached(self):
        c = DeviceBlockCache(budget_bytes=1024)
        c.get_or_build(("small",), lambda: _arr(512))
        c.get_or_build(("big",), lambda: _arr(4096))
        assert c.used_bytes == 512  # big stayed one-shot
        c.get_or_build(("small",), lambda: pytest.fail("small evicted"))

    def test_snapshot(self):
        c = DeviceBlockCache(budget_bytes=1 << 20)
        c.get_or_build(("k",), lambda: _arr(1024))
        snap = c.snapshot()
        assert snap["entries"] == 1 and snap["usedBytes"] == 1024
        assert snap["misses"] == 1
        # an unsharded array sits whole on one device, and shows so
        assert list(snap["perDeviceBytes"].values()) == [1024]


class TestFragmentResidency:
    def test_write_bumps_generation(self, tmp_path):
        """Device blocks are keyed by (uid, generation): a write must
        bump the generation so every cached slab of this fragment stops
        being referenced (TestExecutorResidency checks the slabs)."""
        from pilosa_tpu.storage.fragment import Fragment
        frag = Fragment(str(tmp_path / "frag"), "i", "f", "standard", 0)
        frag.open()
        try:
            from pilosa_tpu.ops.packed import WORDS_PER_SLICE

            def packed_row():
                return frag.pack_row(
                    0, np.zeros(WORDS_PER_SLICE, dtype=np.uint32))

            frag.set_bit(0, 1)
            g0 = frag.device.generation
            before = packed_row()
            frag.set_bit(0, 100)
            assert frag.device.generation > g0
            # the host packed-row cache was invalidated with it
            assert packed_row().sum() != before.sum()
        finally:
            frag.close()

    def test_uid_unique_across_reopen(self, tmp_path):
        from pilosa_tpu.storage.fragment import Fragment
        path = str(tmp_path / "frag")
        frag = Fragment(path, "i", "f", "standard", 0)
        frag.open()
        uid1 = frag.device.uid
        frag.close()
        frag = Fragment(path, "i", "f", "standard", 0)
        frag.open()
        assert frag.device.uid != uid1
        frag.close()


class TestExecutorResidency:
    @pytest.fixture
    def holder_exec(self, tmp_path):
        from pilosa_tpu.executor import Executor
        from pilosa_tpu.models.holder import Holder
        holder = Holder(str(tmp_path))
        holder.open()
        idx = holder.create_index_if_not_exists("i")
        frame = idx.create_frame_if_not_exists("f")
        from pilosa_tpu import SLICE_WIDTH
        for s in range(8):
            for r in (1, 2):
                for j in range(3 - r + 1):
                    frame.set_bit("standard", r, s * SLICE_WIDTH + j)
        ex = Executor(holder, host="h", mesh_min_slices=1)
        yield holder, ex
        holder.close()

    def test_repeat_count_reuses_device_blocks(self, holder_exec):
        holder, ex = holder_exec
        cache = residency.device_cache()
        q = "Count(Intersect(Bitmap(frame=f, rowID=1)," \
            " Bitmap(frame=f, rowID=2)))"
        first = ex.execute("i", q)[0]
        misses_after_first = cache.misses
        again = ex.execute("i", q)[0]
        assert again == first == 8 * 2  # rows 1∩2 share 2 cols/slice
        assert cache.misses == misses_after_first  # no re-upload
        assert ex.device_fallbacks == 0

    def test_slabs_are_spread_over_every_device(self, holder_exec):
        """Leaf slabs and candidate blocks are sharded over the slice
        axis: the snapshot shows an equal share on each device of the
        mesh, adding up to usedBytes (nothing whole on device 0,
        nothing replicated). chip_smoke.py's verdict reads this."""
        import jax
        holder, ex = holder_exec
        cache = residency.device_cache()
        cache.clear()
        ex.execute("i", "Count(Intersect(Bitmap(frame=f, rowID=1),"
                        " Bitmap(frame=f, rowID=2)))")
        ex.execute("i", "TopN(Bitmap(frame=f, rowID=1), frame=f,"
                        " ids=[1, 2])")
        snap = cache.snapshot()
        per = snap["perDeviceBytes"]
        assert len(per) == len(jax.devices()) > 1
        assert set(per.values()) == {snap["usedBytes"] // len(per)}
        assert sum(per.values()) == snap["usedBytes"] > 0

    def test_repeat_topn_reuses_device_blocks(self, holder_exec):
        holder, ex = holder_exec
        cache = residency.device_cache()
        q = "TopN(Bitmap(frame=f, rowID=1), frame=f, ids=[1, 2])"
        first = ex.execute("i", q)[0]
        misses_after_first = cache.misses
        again = ex.execute("i", q)[0]
        assert [(p.id, p.count) for p in first] == \
            [(p.id, p.count) for p in again] == [(1, 24), (2, 16)]
        assert cache.misses == misses_after_first
        assert ex.device_fallbacks == 0

    def test_write_invalidates_leaf_entry(self, holder_exec):
        holder, ex = holder_exec
        q = "Count(Bitmap(frame=f, rowID=1))"
        assert ex.execute("i", q)[0] == 24
        ex.execute("i", "SetBit(frame=f, rowID=1, columnID=500)")
        assert ex.execute("i", q)[0] == 25  # fresh generation → re-pack
        assert ex.device_fallbacks == 0
