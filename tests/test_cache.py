"""Cache tests (reference cache_test.go semantics)."""

from pilosa_tpu.storage.cache import (LRUCache, Pair, RankCache, SimpleCache,
                                      pairs_add, pairs_sort, top_n_heap_merge)


class TestRankCache:
    def test_add_get_top(self):
        c = RankCache(max_entries=10)
        for i, n in [(1, 5), (2, 9), (3, 1)]:
            c.add(i, n)
        c.recalculate()
        assert [p.id for p in c.top()] == [2, 1, 3]
        assert c.get(2) == 9

    def test_threshold_trims_overflow(self):
        c = RankCache(max_entries=5)
        for i in range(20):
            c.bulk_add(i, i + 1)
        c.recalculate()
        top = c.top()
        assert len(top) == 5
        assert [p.count for p in top] == [20, 19, 18, 17, 16]
        # adds below the new threshold are ignored
        before = len(c)
        c.add(99, 1)
        assert len(c) == before

    def test_trim_keeps_the_ranked_ties_and_no_more(self):
        """Rows tied at the threshold: the trim keeps those the ranking
        holds and drops the rest, so the map stays within maxEntries
        and a second ranking of the same counts is the first."""
        c = RankCache(max_entries=50)
        for i in range(1000):
            c.bulk_add(i, 7 if i % 10 else 9)    # 100 nines, 900 sevens
        c.recalculate()
        first = [(p.id, p.count) for p in c.top()]
        assert first == ([(i, 9) for i in range(0, 500, 10)])
        assert len(c) == 50
        c = RankCache(max_entries=150)
        for i in range(1000):
            c.bulk_add(i, 7 if i % 10 else 9)
        c.recalculate()
        first = [(p.id, p.count) for p in c.top()]
        assert first[:100] == [(i, 9) for i in range(0, 1000, 10)]
        assert first[100:] == [(i, 7) for i in range(1, 56) if i % 10]
        assert len(c) == 150
        c.recalculate()
        assert [(p.id, p.count) for p in c.top()] == first
        assert len(c) == 150

    def test_trim_bounds_an_all_tied_map(self):
        c = RankCache(max_entries=100)
        for i in range(10_000):
            c.bulk_add(i, 4)
        c.recalculate()
        assert len(c) == 100
        assert [p.id for p in c.top()] == list(range(100))
        c.recalculate()
        assert [p.id for p in c.top()] == list(range(100))

    def test_ids_sorted(self):
        c = RankCache()
        for i in (5, 1, 9):
            c.bulk_add(i, 10)
        assert c.ids() == [1, 5, 9]


class TestLRUCache:
    def test_eviction(self):
        c = LRUCache(max_entries=2)
        c.add(1, 10)
        c.add(2, 20)
        c.get(1)        # refresh 1
        c.add(3, 30)    # evicts 2
        assert c.get(2) == 0
        assert c.get(1) == 10 and c.get(3) == 30


class TestPairs:
    def test_pairs_add_merges_counts(self):
        a = [Pair(1, 5), Pair(2, 3)]
        b = [Pair(2, 4), Pair(3, 1)]
        merged = {p.id: p.count for p in pairs_add(a, b)}
        assert merged == {1: 5, 2: 7, 3: 1}

    def test_sort_ties_by_id(self):
        got = pairs_sort([Pair(3, 5), Pair(1, 5), Pair(2, 9)])
        assert [p.id for p in got] == [2, 1, 3]

    def test_top_n_heap_merge(self):
        got = top_n_heap_merge([[Pair(1, 5)], [Pair(1, 2), Pair(2, 6)]], 1)
        assert got == [Pair(1, 7)]


class TestSimpleCache:
    def test_fetch_invalidate(self):
        c = SimpleCache()
        c.add(1, "bm")
        assert c.fetch(1) == "bm"
        c.invalidate(1)
        assert c.fetch(1) is None
