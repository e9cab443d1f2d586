"""Executor tests (reference executor_test.go).

Multi-node behavior is tested the same way the reference does: a real
local Executor plus a cluster whose other node is reached through a
scripted fake client asserting the forwarded query and returning canned
results (executor_test.go:473-692).
"""

import pytest

from pilosa_tpu import SLICE_WIDTH
from pilosa_tpu.cluster.topology import new_cluster
from pilosa_tpu.errors import PilosaError
from pilosa_tpu.executor import ExecOptions, Executor
from pilosa_tpu.models.frame import FrameOptions
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.storage.bitmap import Bitmap
from pilosa_tpu.storage.cache import Pair


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "data"))
    h.open()
    yield h
    h.close()


@pytest.fixture
def executor(holder):
    return Executor(holder, host="local")


def must_set(holder, index, frame, row, col, view="standard"):
    idx = holder.create_index_if_not_exists(index)
    f = idx.create_frame_if_not_exists(frame)
    f.set_bit(view, row, col)


class TestBitmapCalls:
    def test_bitmap(self, holder, executor):
        must_set(holder, "i", "general", 10, 3)
        must_set(holder, "i", "general", 10, SLICE_WIDTH + 1)
        res = executor.execute("i", "Bitmap(rowID=10, frame=general)")
        assert list(res[0].bits()) == [3, SLICE_WIDTH + 1]

    def test_bitmap_attaches_row_attrs(self, holder, executor):
        must_set(holder, "i", "general", 10, 3)
        holder.frame("i", "general").row_attr_store.set_attrs(
            10, {"category": "x"})
        res = executor.execute("i", "Bitmap(rowID=10, frame=general)")
        assert res[0].attrs == {"category": "x"}

    def test_inverse_bitmap(self, holder, executor):
        idx = holder.create_index_if_not_exists("i")
        f = idx.create_frame_if_not_exists(
            "f", FrameOptions(inverse_enabled=True))
        f.set_bit("standard", 5, 100)
        f.set_bit("inverse", 100, 5)
        res = executor.execute("i", "Bitmap(columnID=100, frame=f)")
        assert list(res[0].bits()) == [5]

    def test_inverse_bitmap_remote_leg_keeps_slices(self, holder, executor):
        # A forwarded inverse query arrives with explicit slice ids; they
        # must not be replaced by the (empty) locally-computed inverse
        # list.
        idx = holder.create_index_if_not_exists("i")
        f = idx.create_frame_if_not_exists(
            "f", FrameOptions(inverse_enabled=True))
        f.set_bit("inverse", 100, 5)
        res = executor.execute("i", "Bitmap(columnID=100, frame=f)",
                               slices=[0], opt=ExecOptions(remote=True))
        assert list(res[0].bits()) == [5]

    def test_inverse_requires_flag(self, holder, executor):
        must_set(holder, "i", "f", 1, 2)
        with pytest.raises(PilosaError, match="inverse"):
            executor.execute("i", "Bitmap(columnID=2, frame=f)")

    def test_intersect(self, holder, executor):
        for col in (3, 5, SLICE_WIDTH + 2):
            must_set(holder, "i", "general", 1, col)
        for col in (5, SLICE_WIDTH + 2, SLICE_WIDTH + 9):
            must_set(holder, "i", "general", 2, col)
        res = executor.execute(
            "i", "Intersect(Bitmap(rowID=1), Bitmap(rowID=2))")
        assert list(res[0].bits()) == [5, SLICE_WIDTH + 2]

    def test_union(self, holder, executor):
        must_set(holder, "i", "general", 1, 3)
        must_set(holder, "i", "general", 2, 5)
        res = executor.execute("i", "Union(Bitmap(rowID=1), Bitmap(rowID=2))")
        assert list(res[0].bits()) == [3, 5]

    def test_same_fold_over_other_rows_is_not_a_cache_hit(self, holder,
                                                          executor):
        """The materialized-result cache keys on the expression SHAPE
        and the fragments' generations; two Unions of the same shape
        over different rows of one frame share both, so the rows must
        be in the key too (chip_smoke.py's reference comparison caught
        Union(2,3) answering with Union(0,1)'s bits)."""
        for row in range(4):
            must_set(holder, "i", "general", row, 10 + row)
        first = executor.execute(
            "i", "Union(Bitmap(rowID=0), Bitmap(rowID=1))")
        other = executor.execute(
            "i", "Union(Bitmap(rowID=2), Bitmap(rowID=3))")
        assert list(first[0].bits()) == [10, 11]
        assert list(other[0].bits()) == [12, 13]

    def test_difference(self, holder, executor):
        for col in (1, 2, 3):
            must_set(holder, "i", "general", 1, col)
        must_set(holder, "i", "general", 2, 2)
        res = executor.execute(
            "i", "Difference(Bitmap(rowID=1), Bitmap(rowID=2))")
        assert list(res[0].bits()) == [1, 3]

    def test_empty_intersect_errors(self, holder, executor):
        must_set(holder, "i", "general", 1, 1)
        with pytest.raises(PilosaError, match="empty Intersect"):
            executor.execute("i", "Intersect()")

    def test_count(self, holder, executor):
        must_set(holder, "i", "general", 10, 3)
        must_set(holder, "i", "general", 10, SLICE_WIDTH + 1)
        must_set(holder, "i", "general", 10, SLICE_WIDTH + 2)
        res = executor.execute("i", "Count(Bitmap(rowID=10))")
        assert res[0] == 3


class TestSetBit:
    def test_set_and_clear(self, holder, executor):
        holder.create_index_if_not_exists("i").create_frame_if_not_exists(
            "f")
        res = executor.execute("i", "SetBit(rowID=11, frame=f, columnID=2)")
        assert res[0] is True
        res = executor.execute("i", "SetBit(rowID=11, frame=f, columnID=2)")
        assert res[0] is False  # no change
        assert executor.execute("i", "Count(Bitmap(rowID=11, frame=f))") \
            == [1]
        assert executor.execute(
            "i", "ClearBit(rowID=11, frame=f, columnID=2)") == [True]
        assert executor.execute(
            "i", "ClearBit(rowID=11, frame=f, columnID=2)") == [False]

    def test_set_with_timestamp_creates_time_views(self, holder, executor):
        idx = holder.create_index_if_not_exists("i")
        idx.create_frame_if_not_exists("f", FrameOptions(time_quantum="Y"))
        executor.execute(
            "i",
            'SetBit(rowID=1, frame=f, columnID=2,'
            ' timestamp="2017-03-04T10:30")')
        assert set(holder.frame("i", "f").views) == {"standard",
                                                     "standard_2017"}

    def test_set_inverse_pair(self, holder, executor):
        idx = holder.create_index_if_not_exists("i")
        idx.create_frame_if_not_exists(
            "f", FrameOptions(inverse_enabled=True))
        executor.execute("i", "SetBit(rowID=3, frame=f, columnID=9)")
        # Inverse view holds the transpose.
        res = executor.execute("i", "Bitmap(columnID=9, frame=f)")
        assert list(res[0].bits()) == [3]

    def test_missing_frame_errors(self, holder, executor):
        holder.create_index_if_not_exists("i")
        with pytest.raises(PilosaError):
            executor.execute("i", "SetBit(rowID=1, frame=nope, columnID=2)")


class TestRange:
    def test_range_unions_time_views(self, holder, executor):
        idx = holder.create_index_if_not_exists("i")
        idx.create_frame_if_not_exists("f", FrameOptions(time_quantum="YMDH"))
        q = ('SetBit(rowID=1, frame=f, columnID={col},'
             ' timestamp="{ts}")')
        executor.execute("i", q.format(col=1, ts="2017-01-01T00:00"))
        executor.execute("i", q.format(col=2, ts="2017-01-02T00:00"))
        executor.execute("i", q.format(col=3, ts="2017-02-01T00:00"))
        res = executor.execute(
            "i", 'Range(rowID=1, frame=f, start="2017-01-01T00:00",'
                 ' end="2017-01-31T00:00")')
        assert list(res[0].bits()) == [1, 2]

    def test_range_requires_row_field(self, holder, executor):
        idx = holder.create_index_if_not_exists("i")
        idx.create_frame_if_not_exists("f", FrameOptions(time_quantum="Y"))
        with pytest.raises(PilosaError, match="row field"):
            executor.execute(
                "i", 'Range(frame=f, start="2017-01-01T00:00",'
                     ' end="2017-01-31T00:00")')

    def test_range_no_quantum_empty(self, holder, executor):
        must_set(holder, "i", "f", 1, 2)
        res = executor.execute(
            "i", 'Range(rowID=1, frame=f, start="2017-01-01T00:00",'
                 ' end="2017-01-31T00:00")')
        assert res[0].count() == 0


class TestTopN:
    def test_top_n(self, holder, executor):
        idx = holder.create_index_if_not_exists("i")
        idx.create_frame_if_not_exists(
            "f", FrameOptions(cache_type="ranked"))
        f = holder.frame("i", "f")
        for col in range(5):
            f.set_bit("standard", 0, col)
        for col in range(3):
            f.set_bit("standard", 10, col)
        for col in range(4):
            f.set_bit("standard", 2, SLICE_WIDTH + col)
        for frag in f.view("standard").fragments.values():
            frag.recalculate_cache()
        res = executor.execute("i", "TopN(frame=f, n=2)")
        assert res[0] == [Pair(0, 5), Pair(2, 4)]

    def test_top_n_with_src(self, holder, executor):
        idx = holder.create_index_if_not_exists("i")
        idx.create_frame_if_not_exists(
            "f", FrameOptions(cache_type="ranked"))
        f = holder.frame("i", "f")
        for col in (1, 2, 3, 4):
            f.set_bit("standard", 0, col)
        for col in (1, 2):
            f.set_bit("standard", 5, col)
        f.set_bit("standard", 7, 1)
        f.view("standard").fragment(0).recalculate_cache()
        # src = row 0's bits; ranked intersection counts.
        res = executor.execute(
            "i", "TopN(Bitmap(rowID=0, frame=f), frame=f, n=2)")
        assert res[0] == [Pair(0, 4), Pair(5, 2)]
        # Staleness regression (round 5: src-cols memo + count-map
        # cache): mutating a CANDIDATE row must refresh its count on
        # the next query...
        f.set_bit("standard", 5, 3)
        f.view("standard").fragment(0).recalculate_cache()
        res = executor.execute(
            "i", "TopN(Bitmap(rowID=0, frame=f), frame=f, n=2)")
        assert res[0] == [Pair(0, 4), Pair(5, 3)]
        # ...and mutating the SRC row must invalidate the memoized
        # src key (fresh row object) and the map.
        f.set_bit("standard", 0, 9)
        f.set_bit("standard", 7, 9)
        f.view("standard").fragment(0).recalculate_cache()
        res = executor.execute(
            "i", "TopN(Bitmap(rowID=0, frame=f), frame=f, n=3)")
        assert res[0] == [Pair(0, 5), Pair(5, 3), Pair(7, 2)]

    def test_top_n_fill(self, holder, executor):
        """executor_test.go:300-322: the global winner's count must
        aggregate across slices even when the per-slice tops differ —
        the exact phase re-queries every candidate everywhere."""
        idx = holder.create_index_if_not_exists("i")
        idx.create_frame_if_not_exists(
            "f", FrameOptions(cache_type="ranked"))
        f = holder.frame("i", "f")
        for col in (0, 1, 2):
            f.set_bit("standard", 0, col)
        f.set_bit("standard", 0, SLICE_WIDTH)
        f.set_bit("standard", 1, SLICE_WIDTH + 2)
        f.set_bit("standard", 1, SLICE_WIDTH)
        for frag in f.view("standard").fragments.values():
            frag.recalculate_cache()
        res = executor.execute("i", "TopN(frame=f, n=1)")
        assert res[0] == [Pair(0, 4)]

    def test_top_n_fill_small(self, holder, executor):
        """executor_test.go:324-356: row 0 is never any slice's sole
        standout (1 bit/slice over 5 slices vs 2-bit rows per slice)
        yet must win globally with count 5."""
        idx = holder.create_index_if_not_exists("i")
        idx.create_frame_if_not_exists(
            "f", FrameOptions(cache_type="ranked"))
        f = holder.frame("i", "f")
        for s in range(5):
            f.set_bit("standard", 0, s * SLICE_WIDTH)
        f.set_bit("standard", 1, 0)
        f.set_bit("standard", 1, 1)
        f.set_bit("standard", 2, SLICE_WIDTH)
        f.set_bit("standard", 2, SLICE_WIDTH + 1)
        f.set_bit("standard", 3, 2 * SLICE_WIDTH)
        f.set_bit("standard", 3, 2 * SLICE_WIDTH + 1)
        f.set_bit("standard", 4, 3 * SLICE_WIDTH)
        f.set_bit("standard", 4, 3 * SLICE_WIDTH + 1)
        for frag in f.view("standard").fragments.values():
            frag.recalculate_cache()
        res = executor.execute("i", "TopN(frame=f, n=1)")
        assert res[0] == [Pair(0, 5)]

    def test_top_n_int_attr_filter(self, holder, executor):
        """executor_test.go:391-435: attribute filters with INT values
        (filters=[123] against an int64-typed attr), with and without a
        source bitmap, across two slices."""
        idx = holder.create_index_if_not_exists("i")
        idx.create_frame_if_not_exists(
            "f", FrameOptions(cache_type="ranked"))
        f = holder.frame("i", "f")
        f.set_bit("standard", 0, 0)
        f.set_bit("standard", 0, 1)
        f.set_bit("standard", 10, SLICE_WIDTH)
        f.row_attr_store.set_attrs(10, {"category": 123})
        for view in f.views.values():
            for frag in view.fragments.values():
                frag.recalculate_cache()
        res = executor.execute(
            "i", 'TopN(frame="f", n=1, field="category", filters=[123])')
        assert res[0] == [Pair(10, 1)]
        res = executor.execute(
            "i", 'TopN(Bitmap(rowID=10, frame=f), frame="f", n=1,'
                 ' field="category", filters=[123])')
        assert res[0] == [Pair(10, 1)]

    def test_top_n_ids(self, holder, executor):
        idx = holder.create_index_if_not_exists("i")
        idx.create_frame_if_not_exists(
            "f", FrameOptions(cache_type="ranked"))
        f = holder.frame("i", "f")
        for col in range(5):
            f.set_bit("standard", 0, col)
        for col in range(3):
            f.set_bit("standard", 1, col)
        res = executor.execute("i", "TopN(frame=f, ids=[1])")
        assert res[0] == [Pair(1, 3)]


class TestAttrs:
    def test_set_row_attrs(self, holder, executor):
        must_set(holder, "i", "f", 10, 1)
        executor.execute("i", 'SetRowAttrs(rowID=10, frame=f, foo="bar")')
        assert holder.frame("i", "f").row_attr_store.attrs(10) == \
            {"foo": "bar"}

    def test_bulk_set_row_attrs(self, holder, executor):
        must_set(holder, "i", "f", 1, 1)
        res = executor.execute(
            "i",
            'SetRowAttrs(rowID=1, frame=f, a=1)\n'
            'SetRowAttrs(rowID=2, frame=f, b=true)')
        assert res == [None, None]
        store = holder.frame("i", "f").row_attr_store
        assert store.attrs(1) == {"a": 1}
        assert store.attrs(2) == {"b": True}

    def test_set_column_attrs(self, holder, executor):
        must_set(holder, "i", "f", 1, 10)
        executor.execute("i", 'SetColumnAttrs(columnID=10, foo="baz")')
        assert holder.index("i").column_attr_store.attrs(10) == \
            {"foo": "baz"}

    def test_typed_attrs_persist_across_reopen(self, holder, executor):
        """All four reference attr types (attr.go:34-40) through PQL,
        surviving a holder reopen byte-typed (protobuf AttrMap)."""
        must_set(holder, "i", "f", 1, 1)
        executor.execute(
            "i", 'SetRowAttrs(frame="f", rowID=1, active=true,'
                 ' weight=1.5, name="x", rank=9)')
        want = {"active": True, "weight": 1.5, "name": "x", "rank": 9}
        assert holder.frame("i", "f").row_attr_store.attrs(1) == want
        path = holder.path
        holder.close()
        h2 = Holder(path)
        h2.open()
        try:
            got = h2.frame("i", "f").row_attr_store.attrs(1)
            assert got == want
            assert isinstance(got["active"], bool)
            assert isinstance(got["weight"], float)
            assert isinstance(got["rank"], int)
        finally:
            h2.close()
            holder.open()  # fixture teardown closes it again


class FakeClient:
    """Scripted remote transport (reference executor_test.go mock server)."""

    def __init__(self, fn):
        self.fn = fn
        self.calls = []

    def execute_query(self, node, index, query, slices, remote):
        self.calls.append((node.host, index, query, slices, remote))
        return self.fn(node, index, query, slices)


class TestDistributed:
    def _two_node(self, holder, fn, replica_n=1):
        cluster = new_cluster(["local", "remotehost"], replica_n=replica_n)
        client = FakeClient(fn)
        e = Executor(holder, host="local", cluster=cluster, client=client)
        return e, client, cluster

    def test_remote_count_merges(self, holder):
        must_set(holder, "i", "general", 10, 3)  # slice 0 data

        def fn(node, index, query, slices):
            assert query == "Count(Bitmap(frame=\"general\", rowID=10))"
            return [7]

        e, client, cluster = self._two_node(holder, fn)
        # Force 3 slices; remote node owns some of them.
        holder.index("i").set_remote_max_slice(2)
        res = e.execute("i", "Count(Bitmap(rowID=10, frame=general))")
        slice0_local = cluster.fragment_nodes("i", 0)[0].host == "local"
        remote_slices = [s for s in range(3)
                         if cluster.fragment_nodes("i", s)[0].host
                         == "remotehost"]
        # All remote slices arrive grouped into ONE exec call.
        assert len(client.calls) == (1 if remote_slices else 0)
        expected = (1 if slice0_local else 0) + \
            (7 if remote_slices else 0)
        assert res[0] == expected

    def test_remote_bitmap_merges(self, holder):
        must_set(holder, "i", "general", 10, 3)
        holder.index("i").set_remote_max_slice(2)

        def fn(node, index, query, slices):
            bm = Bitmap()
            for s in slices:
                bm.set_bit(s * SLICE_WIDTH + 42)
            return [bm]

        e, client, cluster = self._two_node(holder, fn)
        res = e.execute("i", "Bitmap(rowID=10, frame=general)")
        bits = set(res[0].bits())
        if cluster.fragment_nodes("i", 0)[0].host == "local":
            assert 3 in bits
        for host, index, query, slices, remote in client.calls:
            assert remote is True
            for s in slices:
                assert s * SLICE_WIDTH + 42 in bits

    def test_remote_topn_two_phase(self, holder):
        idx = holder.create_index_if_not_exists("i")
        idx.create_frame_if_not_exists(
            "f", FrameOptions(cache_type="ranked"))
        f = holder.frame("i", "f")
        for col in range(4):
            f.set_bit("standard", 0, col)
        f.view("standard").fragment(0).recalculate_cache()
        idx.set_remote_max_slice(2)

        def fn(node, index, query, slices):
            if "ids=" in query:
                return [[Pair(0, 1), Pair(30, 5)]]  # exact-count phase
            return [[Pair(30, 5)]]

        e, client, cluster = self._two_node(holder, fn)
        res = e.execute("i", "TopN(frame=f, n=2)")
        has_remote = any(cluster.fragment_nodes("i", s)[0].host
                         == "remotehost" for s in range(3))
        slice0_local = cluster.fragment_nodes("i", 0)[0].host == "local"
        assert has_remote  # 3 slices over 2 nodes: some leg is remote
        # Second phase re-queried with the candidate ids.
        assert any("ids=" in c[2] for c in client.calls)
        if slice0_local:
            # local Pair(0,4) + remote phase-2 Pair(0,1) merge to 5.
            assert res[0] == [Pair(0, 5), Pair(30, 5)]
        else:
            # local fragment not owned → only remote results survive.
            assert res[0] == [Pair(30, 5), Pair(0, 1)]

    def test_setbit_forwards_to_owner(self, holder):
        holder.create_index_if_not_exists("i").create_frame_if_not_exists(
            "f")

        def fn(node, index, query, slices):
            assert query.startswith("SetBit(")
            return [True]

        e, client, cluster = self._two_node(holder, fn, replica_n=2)
        res = e.execute("i", "SetBit(rowID=1, frame=f, columnID=3)")
        assert res[0] is True
        # replica_n=2 on 2 nodes → both own slice 0; remote got the call.
        assert len(client.calls) == 1
        # Local write also landed.
        assert holder.fragment("i", "f", "standard", 0).row(1).count() == 1

    def test_remote_flag_stops_forwarding(self, holder):
        holder.create_index_if_not_exists("i").create_frame_if_not_exists(
            "f")

        def fn(node, index, query, slices):
            raise AssertionError("must not forward when remote=True")

        e, client, cluster = self._two_node(holder, fn, replica_n=2)
        res = e.execute("i", "SetBit(rowID=1, frame=f, columnID=3)",
                        opt=ExecOptions(remote=True))
        assert res[0] is True
        assert client.calls == []

    def test_failed_node_retries_on_replica(self, holder):
        must_set(holder, "i", "general", 10, 3)
        holder.index("i").set_remote_max_slice(2)
        attempts = []

        def fn(node, index, query, slices):
            attempts.append(list(slices))
            raise ConnectionError("node down")

        # replica_n=2 on 2 nodes → every slice is owned by both; when the
        # remote leg fails its slices re-map onto the local node.
        e, client, cluster = self._two_node(holder, fn, replica_n=2)
        res = e.execute("i", "Count(Bitmap(rowID=10, frame=general))")
        assert res[0] == 1  # all slices eventually served locally

    def test_attr_write_broadcasts(self, holder):
        must_set(holder, "i", "f", 10, 1)

        def fn(node, index, query, slices):
            assert query == 'SetRowAttrs(foo="bar", frame="f", rowID=10)'
            return [None]

        e, client, cluster = self._two_node(holder, fn)
        e.execute("i", 'SetRowAttrs(rowID=10, frame=f, foo="bar")')
        assert len(client.calls) == 1  # forwarded to the one other node


class TestDeviceCountPath:
    """The mesh-batched Count fast path must agree exactly with the
    per-slice host path on randomized data (and engage when eligible)."""

    def _fill(self, holder, rng, frame="f", rows=(1, 2, 3), slices=3):
        idx = holder.create_index_if_not_exists("i")
        f = idx.create_frame_if_not_exists(frame)
        for row in rows:
            cols = rng.choice(slices * SLICE_WIDTH,
                              size=rng.integers(50, 200), replace=False)
            for col in cols:
                f.set_bit("standard", int(row), int(col))

    def test_matches_host_path(self, holder):
        import numpy as np
        rng = np.random.default_rng(7)
        self._fill(holder, rng)
        queries = [
            'Count(Bitmap(rowID=1, frame=f))',
            'Count(Intersect(Bitmap(rowID=1, frame=f),'
            ' Bitmap(rowID=2, frame=f)))',
            'Count(Union(Bitmap(rowID=1, frame=f), Bitmap(rowID=2, frame=f),'
            ' Bitmap(rowID=3, frame=f)))',
            'Count(Difference(Bitmap(rowID=1, frame=f),'
            ' Bitmap(rowID=2, frame=f), Bitmap(rowID=3, frame=f)))',
            'Count(Union(Intersect(Bitmap(rowID=1, frame=f),'
            ' Bitmap(rowID=2, frame=f)), Bitmap(rowID=3, frame=f)))',
            'Count(Bitmap(rowID=99, frame=f))',  # absent row
        ]
        fast = Executor(holder, host="local", use_mesh=True,
                        mesh_min_slices=1)
        slow = Executor(holder, host="local", use_mesh=False)
        for q in queries:
            assert fast.execute("i", q) == slow.execute("i", q), q

    def test_fast_path_engages(self, holder, monkeypatch):
        import numpy as np
        rng = np.random.default_rng(8)
        self._fill(holder, rng)
        f = holder.frame("i", "f")
        for col in (7, SLICE_WIDTH + 9, 2 * SLICE_WIDTH + 11):
            f.set_bit("standard", 1, col)
            f.set_bit("standard", 2, col)
        ex = Executor(holder, host="local", use_mesh=True,
                      mesh_min_slices=1)
        called = {}
        from pilosa_tpu.parallel import mesh as mesh_mod
        orig = mesh_mod.count_expr_sharded

        def spy(mesh, expr, arrs):
            called["expr"] = expr
            called["n_leaves"] = len(arrs)
            return orig(mesh, expr, arrs)

        monkeypatch.setattr(mesh_mod, "count_expr_sharded", spy)
        res = ex.execute("i", 'Count(Intersect(Bitmap(rowID=1, frame=f),'
                              ' Bitmap(rowID=2, frame=f)))')
        assert called["expr"] == ("and", ("leaf", 0), ("leaf", 1))
        assert called["n_leaves"] == 2
        assert res[0] >= 3  # the three overlap columns, one per slice

    def test_range_on_device_matches_host(self, holder, monkeypatch):
        """Range compiles to an or-fold over its time-view cover
        (executor.go:490-546 semantics on the mesh path)."""
        import numpy as np
        idx = holder.create_index_if_not_exists("i")
        idx.create_frame_if_not_exists(
            "tq", FrameOptions(time_quantum="YMD"))
        rng = np.random.default_rng(13)
        write = Executor(holder, host="local", use_mesh=False)
        for day in (2, 3, 9, 28):
            for col in rng.choice(3 * SLICE_WIDTH, size=40, replace=False):
                write.execute(
                    "i", f'SetBit(rowID=1, frame=tq, columnID={int(col)},'
                         f' timestamp="2017-01-{day:02d}T00:00")')
        queries = [
            'Count(Range(rowID=1, frame=tq,'
            ' start="2017-01-01T00:00", end="2017-02-01T00:00"))',
            'Count(Range(rowID=1, frame=tq,'
            ' start="2017-01-03T00:00", end="2017-01-10T00:00"))',
            # Range composed with a plain Bitmap leaf
            'Count(Intersect(Range(rowID=1, frame=tq,'
            ' start="2017-01-01T00:00", end="2018-01-01T00:00"),'
            ' Bitmap(rowID=1, frame=tq)))',
            # empty cover window
            'Count(Range(rowID=1, frame=tq,'
            ' start="2016-01-01T00:00", end="2016-02-01T00:00"))',
        ]
        fast = Executor(holder, host="local", use_mesh=True,
                        mesh_min_slices=1)
        slow = Executor(holder, host="local", use_mesh=False)
        # Prove the device path actually executes the Range form — a
        # compile regression to None would make fast == slow trivially.
        engaged = []
        from pilosa_tpu.parallel import mesh as mesh_mod
        orig = mesh_mod.count_expr_sharded

        def spy(mesh, expr, arrs):
            engaged.append(len(arrs))
            return orig(mesh, expr, arrs)

        monkeypatch.setattr(mesh_mod, "count_expr_sharded", spy)
        for q in queries:
            assert fast.execute("i", q) == slow.execute("i", q), q
        assert fast.device_fallbacks == 0
        # All 4 engage — the time cover is by WINDOW, not data, so the
        # out-of-data 2016 window still compiles (absent fragments pack
        # as zeros). Jan 3→10 covers exactly 7 day views.
        assert engaged == [1, 7, 2, 1], engaged

    def test_range_without_quantum_falls_back(self, holder):
        """Range on a quantum-less frame isn't device-eligible — must
        still answer through the host path (which owns the semantics:
        empty bitmap)."""
        idx = holder.create_index_if_not_exists("i")
        idx.create_frame_if_not_exists("plain")
        ex = Executor(holder, host="local", use_mesh=True,
                      mesh_min_slices=1)
        ex.execute("i", 'SetBit(rowID=1, frame=plain, columnID=5)')
        res = ex.execute(
            "i", 'Count(Range(rowID=1, frame=plain,'
                 ' start="2017-01-01T00:00", end="2017-02-01T00:00"))')
        assert res[0] == 0


class TestDeviceTopNPath:
    """Mesh-batched TopN exact-count phase must agree with the per-slice
    host path and engage for the eligible form."""

    def _fill(self, holder, slices=3):
        import numpy as np
        rng = np.random.default_rng(11)
        idx = holder.create_index_if_not_exists("i")
        f = idx.create_frame_if_not_exists("f")
        for row in range(6):
            cols = rng.choice(slices * SLICE_WIDTH, size=120, replace=False)
            for col in cols:
                f.set_bit("standard", row, int(col))
        # deterministic overlaps so intersections are non-trivial
        for col in range(0, slices * SLICE_WIDTH, SLICE_WIDTH // 2):
            for row in range(6):
                f.set_bit("standard", row, col)

    def test_topn_matches_host_path(self, holder):
        self._fill(holder)
        fast = Executor(holder, host="local", use_mesh=True,
                        mesh_min_slices=1)
        slow = Executor(holder, host="local", use_mesh=False)
        queries = [
            'TopN(frame=f, n=3)',
            'TopN(frame=f, n=4, ids=[0,1,2,3,4,5])',
            'TopN(Bitmap(rowID=0, frame=f), frame=f, n=4)',
            'TopN(Intersect(Bitmap(rowID=0, frame=f),'
            ' Bitmap(rowID=1, frame=f)), frame=f, n=3)',
        ]
        for q in queries:
            assert fast.execute("i", q) == slow.execute("i", q), q

    def test_topn_all_option_combinations_match_host(self, holder):
        """threshold>1, Tanimoto, and attr filters
        must run the device path with per-slice pruning semantics
        identical to the per-slice host path, at ≥8 slices."""
        self._fill(holder, slices=8)
        store = holder.frame("i", "f").row_attr_store
        for rid in range(6):
            store.set_attrs(rid, {"cat": "x" if rid % 2 == 0 else "y"})
        fast = Executor(holder, host="local", use_mesh=True,
                        mesh_min_slices=1)
        slow = Executor(holder, host="local", use_mesh=False)
        ids = "ids=[0,1,2,3,4,5]"
        src = "Bitmap(rowID=0, frame=f)"
        queries = [
            f'TopN({src}, frame=f, {ids}, threshold=2)',
            f'TopN({src}, frame=f, {ids}, threshold=40)',
            f'TopN({src}, frame=f, {ids}, tanimotoThreshold=5)',
            f'TopN({src}, frame=f, {ids}, tanimotoThreshold=60)',
            f'TopN({src}, frame=f, {ids}, field="cat", filters=["x"])',
            f'TopN({src}, frame=f, {ids}, field="cat", filters=["y"],'
            ' threshold=2)',
            f'TopN({src}, frame=f, {ids}, field="cat", filters=["x"],'
            ' tanimotoThreshold=10)',
            f'TopN({src}, frame=f, {ids}, field="cat", filters=["z"])',
            # no-ids phase with options still goes per-slice, then the
            # refetch phase engages the device with the options cloned
            f'TopN({src}, frame=f, n=3, threshold=2)',
            f'TopN({src}, frame=f, n=3, field="cat", filters=["x"])',
        ]
        for q in queries:
            f_res = fast.execute("i", q)
            s_res = slow.execute("i", q)
            assert [(p.id, p.count) for p in f_res[0]] == \
                [(p.id, p.count) for p in s_res[0]], q
        assert fast.device_fallbacks == 0

    def test_topn_filtered_streaming_matches_host(self, holder,
                                                  monkeypatch):
        """Filtered forms past the resident block budget must stream
        through the chunked filtered program, staying exact."""
        self._fill(holder, slices=8)
        from pilosa_tpu.parallel import mesh as mesh_mod
        # Shrink the resident-block bound so the 8-slice candidate block
        # exceeds it → the executor takes the streaming branch — and the
        # per-dispatch budget, so the stream itself row-chunks.
        monkeypatch.setattr(Executor, "_topn_resident_bytes",
                            staticmethod(lambda: 1 << 20))
        monkeypatch.setattr(mesh_mod, "TOPN_BLOCK_BYTES", 1 << 20)
        fast = Executor(holder, host="local", use_mesh=True,
                        mesh_min_slices=1)
        slow = Executor(holder, host="local", use_mesh=False)
        src = "Bitmap(rowID=0, frame=f)"
        for q in (f'TopN({src}, frame=f, ids=[0,1,2,3,4,5], threshold=2)',
                  f'TopN({src}, frame=f, ids=[0,1,2,3,4,5],'
                  ' tanimotoThreshold=20)'):
            f_res = fast.execute("i", q)
            s_res = slow.execute("i", q)
            assert [(p.id, p.count) for p in f_res[0]] == \
                [(p.id, p.count) for p in s_res[0]], q
        assert fast.device_fallbacks == 0

    def test_exact_phase_engages(self, holder, monkeypatch):
        self._fill(holder)
        ex = Executor(holder, host="local", use_mesh=True,
                      mesh_min_slices=1)
        calls = []
        from pilosa_tpu.parallel import mesh as mesh_mod
        orig = mesh_mod.topn_exact_sharded

        def spy(mesh, expr, rows, leaves):
            calls.append((expr, rows.shape))
            return orig(mesh, expr, rows, leaves)

        monkeypatch.setattr(mesh_mod, "topn_exact_sharded", spy)
        res = ex.execute("i", 'TopN(Bitmap(rowID=0, frame=f), frame=f, n=3)')
        assert calls, "TopN exact phase did not use the mesh path"
        assert calls[-1][0] == ("leaf", 0)
        assert len(res[0]) == 3

    def test_filters_fall_back(self, holder, monkeypatch):
        self._fill(holder)
        holder.frame("i", "f").row_attr_store.set_attrs(0, {"cat": "x"})
        ex = Executor(holder, host="local", use_mesh=True,
                      mesh_min_slices=1)
        from pilosa_tpu.parallel import mesh as mesh_mod

        def boom(*a, **kw):
            raise AssertionError("device path must not engage with filters")

        monkeypatch.setattr(mesh_mod, "topn_exact", boom)
        monkeypatch.setattr(mesh_mod, "topn_exact_sharded", boom)
        res = ex.execute(
            "i", 'TopN(frame=f, n=2, field="cat", filters=["x"],'
                 ' ids=[0,1,2])')
        assert all(p.id == 0 for p in res[0])


class TestBatchedCounts:
    """Consecutive Count calls in one PQL query fuse into ONE mesh
    program (one device dispatch) with shared, deduplicated leaves."""

    def _fill(self, holder, slices=8):
        import numpy as np
        rng = np.random.default_rng(55)
        f = holder.create_index_if_not_exists("i") \
            .create_frame_if_not_exists("f")
        for row in range(4):
            for col in rng.choice(slices * SLICE_WIDTH, size=150,
                                  replace=False):
                f.set_bit("standard", row, int(col))

    QUERY = ("Count(Bitmap(rowID=0, frame=f))"
             " Count(Intersect(Bitmap(rowID=0, frame=f),"
             " Bitmap(rowID=1, frame=f)))"
             " Count(Union(Bitmap(rowID=2, frame=f),"
             " Bitmap(rowID=3, frame=f)))")

    def test_batch_matches_sequential(self, holder):
        self._fill(holder)
        fast = Executor(holder, host="local", use_mesh=True,
                        mesh_min_slices=1)
        slow = Executor(holder, host="local", use_mesh=False)
        assert fast.execute("i", self.QUERY) == \
            slow.execute("i", self.QUERY)
        assert fast.device_fallbacks == 0

    def test_single_dispatch_with_shared_leaves(self, holder,
                                                monkeypatch):
        self._fill(holder)
        ex = Executor(holder, host="local", use_mesh=True,
                      mesh_min_slices=1)
        calls = []
        from pilosa_tpu.parallel import mesh as mesh_mod
        orig = mesh_mod.count_exprs_sharded

        def spy(mesh, exprs, arrs):
            calls.append((exprs, len(arrs)))
            return orig(mesh, exprs, arrs)

        monkeypatch.setattr(mesh_mod, "count_exprs_sharded", spy)
        ex.execute("i", self.QUERY)
        assert len(calls) == 1  # three Counts, one program
        exprs, n_leaves = calls[0]
        assert len(exprs) == 3
        assert n_leaves == 4  # rowID 0 shared between calls 1 and 2
        assert exprs[1] == ("and", ("leaf", 0), ("leaf", 1))

    def test_mixed_calls_batch_only_runs(self, holder, monkeypatch):
        self._fill(holder)
        ex = Executor(holder, host="local", use_mesh=True,
                      mesh_min_slices=1)
        calls = []
        from pilosa_tpu.parallel import mesh as mesh_mod
        orig = mesh_mod.count_exprs_sharded

        def spy(mesh, exprs, arrs):
            calls.append(len(exprs))
            return orig(mesh, exprs, arrs)

        monkeypatch.setattr(mesh_mod, "count_exprs_sharded", spy)
        q = ("Count(Bitmap(rowID=0, frame=f))"
             " Count(Bitmap(rowID=1, frame=f))"
             " SetBit(rowID=9, frame=f, columnID=3)"
             " Count(Bitmap(rowID=2, frame=f))")
        res = ex.execute("i", q)
        # The leading run of 2 fuses; the trailing lone Count runs as
        # the K=1 form through the same program builder.
        assert calls == [2, 1]
        assert res[2] is True and len(res) == 4
        slow = Executor(holder, host="local", use_mesh=False)
        assert res[:2] == slow.execute(
            "i", "Count(Bitmap(rowID=0, frame=f))"
                 " Count(Bitmap(rowID=1, frame=f))")

    def test_cluster_does_not_batch(self, holder, monkeypatch):
        """Batching would bypass remote legs — multi-node clusters
        must keep per-call map-reduce."""
        self._fill(holder, slices=2)
        cluster = new_cluster(["local", "other"])
        ex = Executor(holder, host="local", cluster=cluster,
                      use_mesh=True, mesh_min_slices=1,
                      client=type("C", (), {
                          "execute_query":
                          lambda self, node, index, q, s, remote:
                          [0]})())
        from pilosa_tpu.parallel import mesh as mesh_mod

        def boom(*a, **kw):
            raise AssertionError("batched on a multi-node cluster")

        monkeypatch.setattr(mesh_mod, "count_exprs_sharded", boom)
        ex.execute("i", "Count(Bitmap(rowID=0, frame=f))"
                        " Count(Bitmap(rowID=1, frame=f))")


class TestDeviceMaterializePath:
    """Materializing Union/Intersect/Difference on device (BASELINE
    config 2) must agree bit-for-bit with the per-slice roaring path
    and engage only on wide fan-outs."""

    N_ROWS = 10

    def _fill(self, holder, slices=8):
        import numpy as np
        rng = np.random.default_rng(77)
        f = holder.create_index_if_not_exists("i") \
            .create_frame_if_not_exists("f")
        for row in range(self.N_ROWS):
            cols = rng.choice(slices * SLICE_WIDTH, size=300,
                              replace=False)
            for col in cols:
                f.set_bit("standard", row, int(col))

    def _wide(self, name, rows=None):
        rows = rows if rows is not None else range(self.N_ROWS)
        children = ", ".join(f"Bitmap(rowID={r}, frame=f)" for r in rows)
        return f"{name}({children})"

    def test_wide_calls_match_host(self, holder):
        self._fill(holder)
        fast = Executor(holder, host="local", use_mesh=True,
                        mesh_min_slices=1)
        slow = Executor(holder, host="local", use_mesh=False)
        for q in (self._wide("Union"), self._wide("Intersect"),
                  self._wide("Difference"),
                  self._wide("Union", range(0, self.N_ROWS, 2))):
            f_bits = list(fast.execute("i", q)[0].bits())
            s_bits = list(slow.execute("i", q)[0].bits())
            assert f_bits == s_bits, q
        assert fast.device_fallbacks == 0

    def test_engages_wide_not_narrow(self, holder, monkeypatch):
        self._fill(holder)
        ex = Executor(holder, host="local", use_mesh=True,
                      mesh_min_slices=1)
        calls = []
        from pilosa_tpu.parallel import mesh as mesh_mod
        orig = mesh_mod.materialize_expr_sharded

        def spy(mesh, expr, arrs):
            calls.append(len(arrs))
            return orig(mesh, expr, arrs)

        monkeypatch.setattr(mesh_mod, "materialize_expr_sharded", spy)
        ex.execute("i", self._wide("Union"))
        assert calls == [self.N_ROWS]
        ex.execute("i", "Union(Bitmap(rowID=0, frame=f),"
                        " Bitmap(rowID=1, frame=f))")
        assert calls == [self.N_ROWS]  # narrow fold stayed host-side

    def test_count_over_wide_union_uses_reduce(self, holder):
        """The 3+-leaf fold goes through _eval_expr's lax.reduce path —
        counts must stay exact."""
        self._fill(holder, slices=4)
        fast = Executor(holder, host="local", use_mesh=True,
                        mesh_min_slices=1)
        slow = Executor(holder, host="local", use_mesh=False)
        q = f"Count({self._wide('Union')})"
        assert fast.execute("i", q) == slow.execute("i", q)
        q = f"Count({self._wide('Difference')})"
        assert fast.execute("i", q) == slow.execute("i", q)


class TestDevicePathFuzz:
    """Randomized parity: device mesh Count/TopN vs the host roaring
    path over random expression trees and bit distributions (the
    reference's quick-check style, applied to the TPU fast paths)."""

    def test_random_expressions_agree(self, holder):
        import numpy as np
        rng = np.random.default_rng(1234)
        slices = 4
        idx = holder.create_index_if_not_exists("i")
        f = idx.create_frame_if_not_exists("f")
        n_rows = 5
        for row in range(n_rows):
            # mixed densities: some rows dense in one slice, sparse rest
            dense_slice = int(rng.integers(slices))
            cols = rng.choice(SLICE_WIDTH // 64, size=300, replace=False)
            for col in cols:
                f.set_bit("standard", row,
                          int(dense_slice * SLICE_WIDTH + col))
            cols = rng.choice(slices * SLICE_WIDTH, size=60, replace=False)
            for col in cols:
                f.set_bit("standard", row, int(col))

        # A time-quantum frame so random leaves can also be Range calls
        # (compiled as or-folds over their time-view covers).
        tqf = idx.create_frame_if_not_exists(
            "tqf", FrameOptions(time_quantum="YMD"))
        slow = Executor(holder, host="local", use_mesh=False)
        for day in (1, 5, 14, 27):
            for col in rng.choice(slices * SLICE_WIDTH, size=30,
                                  replace=False):
                slow.execute(
                    "i", f'SetBit(rowID=1, frame=tqf, columnID={int(col)},'
                         f' timestamp="2017-06-{day:02d}T00:00")')

        def rand_leaf():
            if rng.random() < 0.25:
                d0, d1 = sorted(rng.integers(1, 29, size=2).tolist())
                return (f'Range(rowID=1, frame=tqf,'
                        f' start="2017-06-{d0:02d}T00:00",'
                        f' end="2017-06-{d1 + 1:02d}T00:00")')
            return f'Bitmap(rowID={int(rng.integers(n_rows + 1))}, frame=f)'

        def rand_expr(depth):
            if depth == 0 or rng.random() < 0.4:
                return rand_leaf()
            op = rng.choice(["Intersect", "Union", "Difference"])
            k = int(rng.integers(2, 4))
            return f"{op}({', '.join(rand_expr(depth - 1) for _ in range(k))})"

        fast = Executor(holder, host="local", use_mesh=True,
                        mesh_min_slices=1)
        for _ in range(25):
            q = f"Count({rand_expr(2)})"
            assert fast.execute("i", q) == slow.execute("i", q), q
        for _ in range(10):
            ids = sorted(set(int(x) for x in rng.integers(n_rows + 1,
                                                          size=3)))
            q = (f"TopN({rand_expr(1)}, frame=f, n=4,"
                 f" ids={list(ids)})")
            assert fast.execute("i", q) == slow.execute("i", q), q
        # Multi-Count queries fuse into one batched program — parity
        # must hold for random run lengths and shared leaves.
        for _ in range(10):
            k = int(rng.integers(2, 6))
            q = " ".join(f"Count({rand_expr(1)})" for _ in range(k))
            assert fast.execute("i", q) == slow.execute("i", q), q
        assert fast.device_fallbacks == 0


class TestMeshBackendRecovery:
    def test_backend_failure_backs_off_then_recovers(self, holder,
                                                     monkeypatch):
        """A server started during a TPU outage serves host-side, then
        picks the device back up after the backoff window — no restart
        (round-2 pool outages motivated this)."""
        import numpy as np
        rng = np.random.default_rng(3)
        f = holder.create_index_if_not_exists("i") \
            .create_frame_if_not_exists("f")
        for col in rng.choice(8 * SLICE_WIDTH, size=64, replace=False):
            f.set_bit("standard", 1, int(col))
        ex = Executor(holder, host="local", use_mesh=True,
                      mesh_min_slices=1)
        from pilosa_tpu.parallel import mesh as mesh_mod
        orig_make = mesh_mod.make_mesh

        def broken(*a, **kw):
            raise RuntimeError("backend unavailable")

        monkeypatch.setattr(mesh_mod, "make_mesh", broken)
        q = "Count(Bitmap(frame=f, rowID=1))"
        assert ex.execute("i", q)[0] == 64  # host path, correct
        assert ex.device_fallbacks == 1
        assert ex._mesh is None
        # Within the backoff window: no re-probe (make_mesh would raise).
        assert ex.execute("i", q)[0] == 64
        assert ex.device_fallbacks == 1
        # Outage ends + backoff expires → device path resumes.
        monkeypatch.setattr(mesh_mod, "make_mesh", orig_make)
        ex._mesh_failed_until = 0.0
        assert ex.execute("i", q)[0] == 64
        assert ex._mesh is not None


class TestSparseUploadPath:
    """Cold device blocks may ship as bucketed sparse words + device
    densify (PILOSA_TPU_SPARSE_UPLOAD; round-4 cold-path work). Forced
    interpret mode must produce byte-identical results to the dense
    upload on both the Count-leaf and TopN-candidate builders."""

    def _fill(self, holder, slices=3):
        import numpy as np
        rng = np.random.default_rng(21)
        idx = holder.create_index_if_not_exists("i")
        f = idx.create_frame_if_not_exists("f")
        for row in range(5):
            cols = rng.choice(slices * SLICE_WIDTH, size=150,
                              replace=False)
            for col in cols:
                f.set_bit("standard", row, int(col))

    def test_sparse_and_dense_uploads_agree(self, holder, monkeypatch):
        self._fill(holder)
        queries = [
            'Count(Intersect(Bitmap(rowID=0, frame=f),'
            ' Bitmap(rowID=1, frame=f)))',
            'TopN(frame=f, n=3)',
            'TopN(Bitmap(rowID=0, frame=f), frame=f, n=4)',
        ]
        host = Executor(holder, host="local", use_mesh=False)
        want = [host.execute("i", q) for q in queries]

        from pilosa_tpu.parallel.residency import device_cache
        monkeypatch.setenv("PILOSA_TPU_SPARSE_UPLOAD", "interpret")
        device_cache().clear()
        sparse_ex = Executor(holder, host="local", use_mesh=True,
                             mesh_min_slices=1)
        got_sparse = [sparse_ex.execute("i", q) for q in queries]

        monkeypatch.setenv("PILOSA_TPU_SPARSE_UPLOAD", "0")
        device_cache().clear()
        dense_ex = Executor(holder, host="local", use_mesh=True,
                            mesh_min_slices=1)
        got_dense = [dense_ex.execute("i", q) for q in queries]
        assert got_sparse == want
        assert got_dense == want

    def test_gate_rejects_dense_blocks(self):
        """A block with a dense row must take the dense path (sparse
        loses outright by G=128)."""
        import numpy as np
        from pilosa_tpu.ops import packed
        dense_row = packed.unpack_to_bitmap(
            np.full(32768, 7, dtype=np.uint32))
        words = np.zeros(32768, dtype=np.uint32)
        words[[5, 300]] = 1, 2
        sparse_row = packed.unpack_to_bitmap(words)
        sparse, block, _ = packed.pack_slab([dense_row, sparse_row])
        assert sparse is None
        assert (block[0] == 7).all() and (block[1] == words).all()
        sparse2, block2, _ = packed.pack_slab([sparse_row, None])
        assert block2 is None and sparse2[0].shape[-1] == 1


class TestVectorizedHostTopN:
    def test_matches_per_slice_path(self, holder, monkeypatch):
        """The rank-array host leg (one dict per local batch) must
        reproduce the per-slice map path exactly, for plain,
        thresholded, and ids forms."""
        import numpy as np
        rng = np.random.default_rng(31)
        idx = holder.create_index_if_not_exists("i")
        f = idx.create_frame_if_not_exists("f")
        for row in range(30):
            cols = rng.choice(6 * SLICE_WIDTH,
                              size=int(rng.integers(5, 120)),
                              replace=False)
            for col in cols:
                f.set_bit("standard", row, int(col))
        fast = Executor(holder, host="local", use_mesh=False)
        slow = Executor(holder, host="local", use_mesh=False)
        monkeypatch.setattr(slow, "_topn_local_host_fn",
                            lambda *a, **k: None)
        queries = [
            'TopN(frame=f, n=5)',
            'TopN(frame=f, n=31)',
            'TopN(frame=f)',
            'TopN(frame=f, n=6, threshold=40)',
            'TopN(frame=f, n=4, ids=[0,3,7,29])',
            'TopN(frame=f, ids=[1,2,99], threshold=10)',
        ]
        for q in queries:
            assert fast.execute("i", q) == slow.execute("i", q), q

    def test_ranked_cache_falls_back_to_fresh_counts(self, holder,
                                                     monkeypatch):
        """RankCache rankings are rate-limited; the ids-form fast path
        must defer to the per-slice cache.get path there (round-4
        review: stale ranked counts)."""
        import numpy as np
        idx = holder.create_index_if_not_exists("r")
        f = idx.create_frame_if_not_exists(
            "rf", FrameOptions(cache_type="ranked"))
        for col in range(5):
            f.set_bit("standard", 0, col)
        ex = Executor(holder, host="local", use_mesh=False)
        got = ex.execute("r", 'TopN(frame=rf, n=5, ids=[0])')
        assert [(p.id, p.count) for p in got[0]] == [(0, 5)]
        # mutate within the rank-limiter window; counts must be fresh
        for col in range(5, 9):
            f.set_bit("standard", 0, col)
        got = ex.execute("r", 'TopN(frame=rf, n=5, ids=[0])')
        assert [(p.id, p.count) for p in got[0]] == [(0, 9)]

    def test_ids_form_survives_empty_cache(self, holder):
        """A lost .cache sidecar (empty rank cache) must take the
        recount fallback, not IndexError (round-4 review)."""
        idx = holder.create_index_if_not_exists("e")
        f = idx.create_frame_if_not_exists("ef")
        for col in range(4):
            f.set_bit("standard", 2, col)
        frag = holder.fragment("e", "ef", "standard", 0)
        frag.cache._od.clear()           # simulate lost sidecar
        frag.cache._ranked = None
        ex = Executor(holder, host="local", use_mesh=False)
        got = ex.execute("e", 'TopN(frame=ef, n=5, ids=[2, 7])')
        assert [(p.id, p.count) for p in got[0]] == [(2, 4)]
