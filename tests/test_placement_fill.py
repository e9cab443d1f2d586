"""Placement of a read with cold leaves, and single-flight fills.

A residency fill is a one-off: the leg that finds a kept slab cold
fills it, and every later read of the row gets the fill back. So
host-versus-device is decided on what a read of RESIDENT leaves costs;
pack + upload stay in the price only where every query pays them (a
streaming leg, a slab larger than the whole budget). Priced against
the read, the fill vetoed the leg, the host answer filled nothing, and
the next read was vetoed again (PERF.md, PR 32). Fills are
single-flight by key: eight clients asking for one cold row pack it
once.

The calibration injected here is the chip's (PERF.md, PR 21): one
dispatch + fetch 1 ms, roaring count 15 GB/s, 85 µs a fragment row of
the host walk (PR 31), pack 200 MB/s, upload 6 GB/s, the popcount
kernel 700 GB/s.
"""

import threading

import numpy as np
import pytest

from pilosa_tpu import SLICE_WIDTH
from pilosa_tpu.parallel import residency
from pilosa_tpu.parallel.costmodel import Calibration, CostModel

CHIP = dict(sync_s=1.0e-3, host_bps=1.5e10, host_visit_s=8.5e-5,
            pack_bps=2.0e8, upload_bps=6.0e9, device_bps=7.0e11)
JOIN_S = 60.0


def chip_model() -> CostModel:
    return CostModel(Calibration(**CHIP))


@pytest.fixture
def cache(monkeypatch):
    """A residency cache of this test's own (the process-wide one
    carries other tests' slabs and counters)."""
    def install(budget_bytes: int = 1 << 30):
        c = residency.DeviceBlockCache(budget_bytes)
        monkeypatch.setattr(residency, "_device_cache", c)
        return c
    return install


def _holder(tmp_path, n_slices: int, n_rows: int, bits: int = 64,
            seed: int = 7):
    """(holder, {row: sorted columns}): ``bits`` random columns a slice
    in every row, plus one column every row shares."""
    from pilosa_tpu.models.holder import Holder

    rng = np.random.default_rng(seed)
    holder = Holder(str(tmp_path))
    holder.open()
    frame = holder.create_index("i").create_frame("f")
    cols_of = {}
    base = np.arange(n_slices, dtype=np.uint64) * np.uint64(SLICE_WIDTH)
    for row in range(n_rows):
        offs = rng.integers(1, SLICE_WIDTH // 8, size=(n_slices, bits),
                            dtype=np.uint64)
        cols = np.unique(np.concatenate(
            [(base[:, None] + offs).ravel(), base]))
        frame.import_bits(np.full(len(cols), row, dtype=np.uint64), cols)
        cols_of[row] = cols
    return holder, cols_of


def _executor(holder, **kw):
    from pilosa_tpu.executor import Executor
    ex = Executor(holder, host="h", **kw)
    # (conftest turns the model off for determinism: injected here.)
    ex._cost_model_enabled = True
    ex.cost_model = chip_model()
    return ex


def _count(rows) -> str:
    return "Count(Intersect(%s))" % ", ".join(
        f'Bitmap(frame="f", rowID={r})' for r in rows)


def _touched(cache) -> int:
    """Residency look-ups so far: a device-served leg counts one a
    leaf (hit or miss), a leg kept on the host leaves no trace."""
    return cache.hits + cache.misses


class TestColdLeafIsFilledByItsFirstRead:
    @pytest.mark.parametrize("n_slices", [32, 256])
    def test_first_request_over_cold_rows_is_device_served(
            self, tmp_path, cache, n_slices):
        c = cache()
        holder, cols_of = _holder(tmp_path, n_slices, 2, bits=8)
        ex = _executor(holder)
        want = len(np.intersect1d(cols_of[0], cols_of[1]))
        try:
            slices = list(range(n_slices))
            assert ex.execute("i", _count([0, 1]), slices) == [want]
            assert ex.cost_vetoes == 0
            assert (c.misses, c.fills, c.hits) == (2, 2, 0)
            # the same rows again: resident, nothing packed
            assert ex.execute("i", _count([1, 0]), slices) == [want]
            assert ex.cost_vetoes == 0 and ex.device_fallbacks == 0
            assert (c.misses, c.fills) == (2, 2) and c.hits >= 2
        finally:
            ex.close()
            holder.close()

    def test_no_fixed_point_over_a_working_set_at_twice_the_budget(
            self, tmp_path, cache):
        """50 Zipf-drawn reads over 16 rows of 4 MiB slabs against a
        32 MiB budget: every one device-served, the LRU evicts, and
        every answer equals the numpy count over the loaded columns."""
        n_slices, n_rows = 32, 16
        c = cache(8 * n_slices * 128 * 1024)
        holder, cols_of = _holder(tmp_path, n_slices, n_rows, bits=32)
        ex = _executor(holder)
        rng = np.random.default_rng(99)
        p = 1.0 / np.arange(1, n_rows + 1) ** 0.99
        p /= p.sum()
        try:
            slices = list(range(n_slices))
            for _ in range(50):
                rows = rng.choice(n_rows, size=int(rng.integers(2, 5)),
                                  replace=False, p=p)
                want = cols_of[int(rows[0])]
                for r in rows[1:]:
                    want = np.intersect1d(want, cols_of[int(r)])
                before = _touched(c)
                got = ex.execute("i", _count(rows.tolist()), slices)
                assert got == [len(want)], rows
                assert _touched(c) - before == len(rows), rows
            assert ex.cost_vetoes == 0 and ex.device_fallbacks == 0
            assert c.evictions > 0
            assert c.used_bytes <= c.budget_bytes
            assert c.fills == c.misses > n_rows // 2
        finally:
            ex.close()
            holder.close()


    def test_the_planner_prices_the_host_walk_too(self, tmp_path, cache):
        """Two sparse rows at 32 slices are a few KB of host bytes,
        microseconds by the byte term: the planner's hint kept such
        reads on the host (191 of share8's 200 warm requests on the
        chip; PERF.md, PR 32) before the executor's gate saw them. The
        walk is 64 fragment rows whatever they hold, and the planner
        prices it as the gate does."""
        c = cache()
        n_slices = 32
        holder, cols_of = _holder(tmp_path, n_slices, 2, bits=4)
        ex = _executor(holder)
        ex.planner.calibration = ex.cost_model.cal   # as calibrate() does
        want = len(np.intersect1d(cols_of[0], cols_of[1]))
        try:
            assert ex.execute("i", _count([0, 1]),
                              list(range(n_slices))) == [want]
            assert c.fills == 2 and ex.cost_vetoes == 0
            assert ex.planner.snapshot()["decisions"].get(
                "placement", 0) == 0
            # with a host path that cost its bytes alone, it would
            c.clear()
            ex.planner.calibration = Calibration(
                **dict(CHIP, host_visit_s=0.0))
            assert ex.execute("i", _count([1, 0]),
                              list(range(n_slices))) == [want]
            assert ex.planner.snapshot()["decisions"]["placement"] == 1
            # the hint is a veto by the model's prices: counted as one,
            # its host leg a sample held against the planner's price
            assert c.fills == 2 and ex.cost_vetoes == 1
            assert ex.cost_model.drift_snapshot()["host"]["n"] == 1
        finally:
            ex.close()
            holder.close()

    def test_a_filling_leg_is_no_sample_of_the_drift_loop(
            self, tmp_path, cache):
        cache()
        n_slices = 32
        holder, _ = _holder(tmp_path, n_slices, 2, bits=8)
        ex = _executor(holder)
        try:
            slices = list(range(n_slices))
            ex.execute("i", _count([0, 1]), slices)
            assert ex.cost_model.drift_snapshot()["device"]["n"] == 0
            ex.execute("i", _count([1, 0]), slices)
            assert ex.cost_model.drift_snapshot()["device"]["n"] == 1
            assert not ex._timed_legs       # nothing left in flight
        finally:
            ex.close()
            holder.close()


class TestWhatStillPaysItsPackOnEveryQuery:
    def test_a_slab_larger_than_the_budget_keeps_its_price(
            self, tmp_path, cache):
        """get_or_build returns such a slab uncached, so every query
        would pack it again: 8 MiB at 200 MB/s against a 0.6 ms host
        answer is vetoed, every time."""
        n_slices = 32
        c = cache(1 << 20)      # smaller than one 4 MiB slab
        holder, cols_of = _holder(tmp_path, n_slices, 2, bits=8)
        ex = _executor(holder)
        want = len(np.intersect1d(cols_of[0], cols_of[1]))
        try:
            for i in range(3):
                assert ex.execute("i", _count([0, 1]),
                                  list(range(n_slices))) == [want]
                assert ex.cost_vetoes == i + 1
            assert _touched(c) == 0 and c.fills == 0
        finally:
            ex.close()
            holder.close()

    def test_a_streaming_leg_keeps_its_price(self):
        m = chip_model()
        nbytes = 2 * 32 * 128 * 1024
        assert m.device_pays(nbytes)
        for _ in range(20):
            assert not m.device_pays(nbytes, cold_bytes=nbytes,
                                     streaming=True)

    def test_the_static_floor_and_the_leaf_set_guard_stay(
            self, tmp_path, cache):
        c = cache()
        holder, cols_of = _holder(tmp_path, 32, 2, bits=8)
        ex = _executor(holder)
        want = len(np.intersect1d(cols_of[0], cols_of[1]))
        try:
            # below mesh_min_slices (8): the host serves, unpriced
            few = list(range(4))
            in_few = [cols[cols < 4 * SLICE_WIDTH]
                      for cols in (cols_of[0], cols_of[1])]
            assert ex.execute("i", _count([0, 1]), few) == [
                len(np.intersect1d(*in_few))]
            assert _touched(c) == 0 and ex.cost_vetoes == 0
            # a leaf set over the device budget: the host serves
            ex._MATERIALIZE_DEVICE_BYTES = 4 << 20
            assert ex.execute("i", _count([0, 1]),
                              list(range(32))) == [want]
            assert c.fills == 0 and c.hits == 0
            assert ex.device_fallbacks == 0
        finally:
            ex.close()
            holder.close()


class TestFillsAreSingleFlight:
    def test_eight_threads_asking_one_cold_key_build_once(self):
        import jax.numpy as jnp
        c = residency.DeviceBlockCache(1 << 20)
        started, release = threading.Event(), threading.Event()
        builds = []

        def build():
            builds.append(threading.get_ident())
            started.set()
            assert release.wait(JOIN_S)
            return jnp.arange(8, dtype=jnp.uint32)

        got = [None] * 8

        def ask(i):
            got[i] = c.get_or_build(("k",), build)

        first = threading.Thread(target=ask, args=(0,))
        first.start()
        assert started.wait(JOIN_S)
        rest = [threading.Thread(target=ask, args=(i,))
                for i in range(1, 8)]
        for t in rest:
            t.start()
        for _ in range(2000):       # until all seven wait for the build
            if c.fill_waits == 7:
                break
            threading.Event().wait(0.005)
        assert c.fill_waits == 7
        release.set()
        for t in [first] + rest:
            t.join(JOIN_S)
            assert not t.is_alive()
        assert len(builds) == 1
        assert all(a is got[0] for a in got) and got[0] is not None
        snap = c.snapshot()
        assert (snap["fills"], snap["fillWaits"], snap["misses"],
                snap["hits"], snap["entries"]) == (1, 7, 8, 0, 1)
        assert snap["fillSeconds"] > 0
        assert c.get_or_build(("k",), build) is got[0]
        assert len(builds) == 1 and c.hits == 1

    def test_a_failing_build_wakes_its_waiters_and_the_next_builds(self):
        import jax.numpy as jnp
        c = residency.DeviceBlockCache(1 << 20)
        started, release = threading.Event(), threading.Event()

        def failing():
            started.set()
            assert release.wait(JOIN_S)
            raise RuntimeError("pack failed")

        errors = []

        def ask():
            try:
                c.get_or_build(("k",), failing)
            except RuntimeError as e:
                errors.append(e)

        threads = [threading.Thread(target=ask)]
        threads[0].start()
        assert started.wait(JOIN_S)
        threads += [threading.Thread(target=ask) for _ in range(3)]
        for t in threads[1:]:
            t.start()
        for _ in range(2000):
            if c.fill_waits == 3:
                break
            threading.Event().wait(0.005)
        assert c.fill_waits == 3
        release.set()
        for t in threads:
            t.join(JOIN_S)
            assert not t.is_alive()
        assert len(errors) == 4
        assert all(str(e) == "pack failed" for e in errors)
        assert c.snapshot()["entries"] == 0
        # nothing is left in flight: the next request builds again
        arr = c.get_or_build(("k",), lambda: jnp.zeros(4, jnp.uint32))
        assert arr.shape == (4,)
        assert (c.fills, c.snapshot()["entries"]) == (2, 1)

    def test_a_write_between_two_requests_is_a_new_key_and_a_new_build(
            self, tmp_path, cache):
        """The key embeds the row's token, read before the fragments
        are resolved: a request made after a write to the row never
        waits for, or is handed, a slab built under the token before
        it, and the write dropped that slab."""
        c = cache()
        n_slices = 32
        holder, cols_of = _holder(tmp_path, n_slices, 2, bits=8)
        ex = _executor(holder)
        slices = list(range(n_slices))
        want = len(np.intersect1d(cols_of[0], cols_of[1]))
        mesh = ex._mesh_or_none()
        leaf = ("f", "standard", 0)
        try:
            assert ex.execute("i", _count([0, 1]), slices) == [want]
            key0 = ex._leaf_cache_key(mesh, "i", leaf, slices)
            assert c.fills == 2
            col = int(SLICE_WIDTH - 3)      # set in neither row
            for row in (0, 1):
                assert ex.execute(
                    "i", f'SetBit(frame="f", rowID={row},'
                         f' columnID={col})') == [True]
            key1 = ex._leaf_cache_key(mesh, "i", leaf, slices)
            assert key0 != key1 and not c.contains(key0)
            assert not c.contains(key1)
            assert c.retired_by_write == 2
            # the acknowledged write is visible to the next read, which
            # builds both slabs again under the new token
            assert ex.execute("i", _count([0, 1]), slices) == [want + 1]
            assert c.fills == 4 and c.contains(key1)
            assert ex.cost_vetoes == 0
        finally:
            ex.close()
            holder.close()

    def test_clients_asking_the_same_cold_rows_fill_each_once(
            self, tmp_path, cache):
        """Eight threads, the same two cold rows, through the executor:
        two fills, everyone answered exactly."""
        c = cache()
        n_slices = 32
        holder, cols_of = _holder(tmp_path, n_slices, 2, bits=8)
        ex = _executor(holder)
        want = len(np.intersect1d(cols_of[0], cols_of[1]))
        go = threading.Barrier(8)
        got = [None] * 8

        def ask(i):
            go.wait(JOIN_S)
            got[i] = ex.execute("i", _count([0, 1]),
                                list(range(n_slices)))

        try:
            ex._mesh_or_none()      # one mesh before the threads start
            threads = [threading.Thread(target=ask, args=(i,))
                       for i in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(JOIN_S)
                assert not t.is_alive()
            assert got == [[want]] * 8
            assert c.fills == 2
            assert c.misses == 2 + c.fill_waits
            assert c.hits + c.misses == 16
            assert ex.cost_vetoes == 0 and ex.device_fallbacks == 0
        finally:
            ex.close()
            holder.close()


# -- the benchmark's readers of set-up's two counters ---------------------------


def _run(before):
    from cellbench import run_cell
    run = run_cell.Run()
    run.before = None if before is None else {"status": {},
                                              "vars": dict(before)}
    return run


SETUP_METRICS = {
    # name: (reader module, layer, unit)
    "setup_host_legs": ("setup_host_legs", "executor + routing", "count"),
    "setup_fill_s": ("setup_fill", "residency", "s"),
}


@pytest.mark.parametrize("before, want", [
    ({"costModelVetoes": 0}, 0.0),
    ({"costModelVetoes": 73, "deviceBlockCache": {}}, 73.0),
    ({}, None),                     # a program without the counter
    (None, None),                   # an untraced run
], ids=["none", "73", "absent", "untraced"])
def test_setup_host_legs_reads_the_vetoes_of_set_up(before, want):
    from cellbench.readers import setup_host_legs
    assert setup_host_legs.read(_run(before)) == want


@pytest.mark.parametrize("before, want", [
    ({"deviceBlockCache": {"fills": 24, "fillSeconds": 41.25}}, 41.25),
    ({"deviceBlockCache": {"fills": 0, "fillSeconds": 0.0}}, 0.0),
    # the parent: the cache is there, the counter is not
    ({"deviceBlockCache": {"hits": 5, "misses": 60}}, None),
    ({}, None),
    (None, None),
], ids=["41s", "0", "parent", "absent", "untraced"])
def test_setup_fill_s_reads_the_builders_seconds_of_set_up(before, want):
    from cellbench.readers import setup_fill
    assert setup_fill.read(_run(before)) == want


@pytest.mark.parametrize("name", sorted(SETUP_METRICS))
def test_setup_metrics_are_declared_as_their_files_say(name):
    import json
    import os
    reader, layer, unit = SETUP_METRICS[name]
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(root, "cellbench", "metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    for k, v in entry.items():
        assert k == "workloads" or spec[k] == v, k
    assert spec["reader"] == reader
    assert entry["workloads"] == ["c4-count-hot", "c4-count-hot-solo",
                                  "c4-count-hot-mesh4"]
    assert (entry["moves"], entry["layer"], entry["unit"],
            entry["better"], entry["source"]) == (
        "setup_s", layer, unit, "lower", "program_counter")
    # every listed cell reports the end-to-end metric it moves
    setup = next(m for m in bench["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup
