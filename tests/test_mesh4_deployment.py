"""The deployment ``baseline-c4-mesh4`` (ISSUE 27) at a small size: one
server, the slice axis of every leaf slab sharded over a (1, n) device
mesh, each Count merged by the all-reduce inside its program.

On the CPU's 8 virtual devices (conftest.py). The benchmark's cell
``c4-count-hot-mesh4`` holds it on four chips at 256 slices; here the
same requests — ``Count(Intersect(k rows))``, k = 2, 3, 4 — go through
the executor on meshes of 1, 4 and 8 devices and are held to numpy
counts over the same seeded bits. The executor has no option that names
a mesh width (a server takes every device JAX shows): the tests hand it
``make_mesh(n)`` where a server would have called ``make_mesh()``.
"""

import http.client
import itertools
import json

import numpy as np
import pytest

from pilosa_tpu import SLICE_WIDTH
from pilosa_tpu.executor import ExecOptions, Executor
from pilosa_tpu.models.frame import FrameOptions
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.models.view import VIEW_STANDARD
from pilosa_tpu.obs import accounting
from pilosa_tpu.ops import packed
from pilosa_tpu.parallel import mesh as mesh_mod
from pilosa_tpu.parallel import programs, residency
from pilosa_tpu.sched import QueryContext
from pilosa_tpu.server.server import Server

ROWS = 6
DOMAIN = 64            # candidate columns a slice
STRIDE = 16411         # spreads them over the slice's 16 containers
SEED = 27


def _bits(n_slices: int) -> np.ndarray:
    """bool [ROWS, n_slices, DOMAIN]: row r has candidate column j of
    slice s. Dense enough that a 4-row intersection is not empty."""
    rng = np.random.default_rng(SEED + n_slices)
    return rng.random((ROWS, n_slices, DOMAIN)) < 0.6


def _load(holder, bits: np.ndarray, index: str = "i"):
    fr = holder.create_index_if_not_exists(index) \
        .create_frame_if_not_exists("f", FrameOptions())
    r, s, j = np.nonzero(bits)
    fr.import_bits(r.astype(np.uint64),
                   (s * SLICE_WIDTH + j * STRIDE).astype(np.uint64))


def _pql(rows) -> str:
    return "Count(Intersect(%s))" % ", ".join(
        f"Bitmap(frame=f, rowID={r})" for r in rows)


def _want(bits: np.ndarray, rows) -> int:
    return int(np.logical_and.reduce(bits[list(rows)], axis=0).sum())


def _executor(holder, n_dev: int) -> Executor:
    ex = Executor(holder, host="local", use_mesh=True, mesh_min_slices=1)
    ex._mesh = mesh_mod.make_mesh(n_dev)
    return ex


def _served(ex, pql: str):
    """(answer, the request's cost summary: what X-Pilosa-Stats holds)."""
    ctx = QueryContext(pql=pql)
    accounting.attach(ctx)
    return ex.execute("i", pql, None, ExecOptions(ctx=ctx))[0], \
        ctx.cost.summary()


@pytest.fixture(autouse=True)
def _fresh_residency():
    residency.device_cache().clear()
    yield
    residency.device_cache().clear()


@pytest.fixture(scope="module", params=[8, 64, 256],
                ids=lambda n: f"{n}slices")
def loaded(request, tmp_path_factory):
    n_slices = request.param
    holder = Holder(str(tmp_path_factory.mktemp(f"mesh4_{n_slices}")))
    holder.open()
    bits = _bits(n_slices)
    _load(holder, bits)
    yield holder, bits
    holder.close()


@pytest.fixture
def holder64(tmp_path):
    holder = Holder(str(tmp_path / "data"))
    holder.open()
    bits = _bits(64)
    _load(holder, bits)
    yield holder, bits
    holder.close()


# -- (a) the differential -----------------------------------------------------

ROW_SETS = [(0, 1), (2, 5), (0, 2, 4), (1, 3, 5), (0, 1, 2, 3),
            (2, 3, 4, 5)]


@pytest.mark.parametrize("n_dev", [1, 4, 8])
def test_counts_equal_numpy_on_every_mesh_width(loaded, n_dev):
    """k = 2, 3, 4 at 8 / 64 / 256 slices on meshes of 1 / 4 / 8
    devices: every answer is the numpy count, so all widths agree, and
    every one came from ONE device program on a mesh of that width."""
    holder, bits = loaded
    ex = _executor(holder, n_dev)
    try:
        for rows in ROW_SETS:
            got, stats = _served(ex, _pql(rows))
            assert got == _want(bits, rows) > 0, rows
            assert stats["devicePrograms"] == 1
            assert stats["meshDevices"] == n_dev
        assert ex.device_fallbacks == 0 and ex.cost_vetoes == 0
    finally:
        ex.close()


# -- (b) the share of each device ---------------------------------------------

@pytest.mark.parametrize("n_slices, n_dev", [(64, 4), (256, 4), (8, 4),
                                            (64, 8)])
def test_the_devices_partial_counts_add_up_to_the_answer(tmp_path,
                                                         n_slices, n_dev):
    """The guide's share test: each device holds ``bucket / n`` slices of
    every slab (its contiguous run of the slice axis), the partial count
    of one expression over each device's own shards — what the program
    computes before its all-reduce — is that device's share, and the
    shares add up to the whole answer."""
    holder = Holder(str(tmp_path / "data"))
    holder.open()
    bits = _bits(n_slices)
    _load(holder, bits)
    rows = (0, 2, 3)
    ex = _executor(holder, n_dev)
    try:
        got, _ = _served(ex, _pql(rows))
        mesh = ex._mesh
        leaves = [("f", VIEW_STANDARD, r) for r in rows]
        _, slabs, cold = ex._leaf_lookup(mesh, "i", leaves,
                                         list(range(n_slices)))
        assert cold == 0        # the read above left them resident
        bucket = programs.slice_bucket(n_slices, n_dev)
        partial = {}
        for slab in slabs:
            assert slab.shape == (bucket, packed.WORDS_PER_SLICE)
            assert len(slab.addressable_shards) == n_dev
        for d, dev in enumerate(mesh.devices.flat):
            shards = [next(s for s in slab.addressable_shards
                           if s.device == dev) for slab in slabs]
            for s in shards:
                assert s.data.shape[0] == bucket // n_dev
                assert s.index[0] == slice(d * bucket // n_dev,
                                           (d + 1) * bucket // n_dev)
            words = np.bitwise_and.reduce(
                [np.asarray(s.data) for s in shards])
            partial[d] = int(np.unpackbits(words.view(np.uint8)).sum())
            lo, hi = d * bucket // n_dev, (d + 1) * bucket // n_dev
            assert partial[d] == int(np.logical_and.reduce(
                bits[list(rows), lo:hi], axis=0).sum())
        assert sum(partial.values()) == got == _want(bits, rows)
        held = residency.device_cache().snapshot()
        assert len(set(held["perDeviceBytes"].values())) == 1
        assert sum(held["perDeviceBytes"].values()) == held["usedBytes"]
    finally:
        ex.close()
        holder.close()


# -- (c) slab origins ---------------------------------------------------------

class TestSlabOrigins:
    @pytest.mark.parametrize("n_dev", [1, 4])
    def test_no_new_specialisation_for_any_mix_of_origins(self, n_dev):
        """A slab uploaded dense (``device_put``) and one uploaded
        sparse (the ``densify`` program's output) carry one sharding on
        a one-device AND on a four-device mesh, so after a k-leaf
        count program's first call no mix of origins among its leaves
        compiles again (PR 26's 2^k re-specialisations)."""
        mesh = mesh_mod.make_mesh(n_dev)
        n = 8
        dense = np.zeros((n, packed.WORDS_PER_SLICE), dtype=np.uint32)
        dense[:, :4] = 7
        thin = np.zeros(packed.WORDS_PER_SLICE, dtype=np.uint32)
        thin[1:4] = 5, 6, 7
        sparse, _, _ = packed.pack_slab([packed.unpack_to_bitmap(thin)] * n)
        put = mesh_mod.shard_slices(mesh, dense)
        made = mesh_mod.densify_sharded(
            mesh, *sparse, interpret=True)
        assert put.sharding == made.sharding
        assert len(made.addressable_shards) == n_dev
        host = {id(put): np.asarray(put), id(made): np.asarray(made)}
        for k in (2, 3):
            expr = ("leaf", 0)
            for i in range(1, k):
                expr = ("and", expr, ("leaf", i))
            mesh_mod.count_expr_sharded(mesh, expr, [put] * k)
            compiled = mesh_mod.compile_stats()["firstCalls"]
            for mix in itertools.product((put, made), repeat=k):
                want = np.bitwise_and.reduce([host[id(a)] for a in mix])
                assert mesh_mod.count_expr_sharded(
                    mesh, expr, list(mix)) == int(
                        np.unpackbits(want.view(np.uint8)).sum())
            assert mesh_mod.compile_stats()["firstCalls"] == compiled


# -- (d) what says how wide a mesh served a request ---------------------------

def _post(conn, path, body):
    conn.request("POST", path, body)
    resp = conn.getresponse()
    return resp, resp.read()


def _get(conn, path) -> dict:
    conn.request("GET", path)
    return json.loads(conn.getresponse().read())


@pytest.fixture
def server(tmp_path, monkeypatch):
    """A real server on a real socket; its executor forms its own mesh
    at the first device call, from every (virtual) device."""
    monkeypatch.setenv("PILOSA_TPU_MESH_MIN_SLICES", "1")
    s = Server(str(tmp_path / "s"), host="127.0.0.1:0",
               anti_entropy_interval=0, polling_interval=0)
    s.open()
    conn = http.client.HTTPConnection(s.host, timeout=30)
    try:
        assert _post(conn, "/index/i", b"{}")[0].status == 200
        assert _post(conn, "/index/i/frame/f", b"{}")[0].status == 200
        for row in (1, 2):
            for col in (3, 5, 1 << 20 | 7, 3 << 20 | 9):
                _post(conn, "/index/i/query",
                      f'SetBit(frame="f", rowID={row},'
                      f' columnID={col})'.encode())
        yield s, conn
    finally:
        conn.close()
        s.close()


COUNT = (b'Count(Intersect(Bitmap(frame="f", rowID=1),'
         b' Bitmap(frame="f", rowID=2)))')


class TestMeshWidthIsVisible:
    def test_stats_vars_and_span_name_the_mesh(self, server):
        s, conn = server
        assert _get(conn, "/debug/vars")["mesh"] is None
        ran = mesh_mod.programs_run()
        resp, data = _post(conn, "/index/i/query?trace=1", COUNT)
        assert json.loads(data)["results"] == [4]
        stats = json.loads(resp.getheader("X-Pilosa-Stats"))
        made = mesh_mod.make_mesh()
        assert stats["devicePrograms"] == 1
        assert stats["meshDevices"] == made.devices.size == 8
        got = _get(conn, "/debug/vars")["mesh"]
        assert got["shape"] == list(made.devices.shape) == [1, 8]
        assert got["devices"] == 8
        assert got["builds"] == 1 and got["failures"] == 0
        assert got["programsRun"] == ran + 1
        # the kept trace's map_reduce span carries the width
        qid = resp.getheader("X-Pilosa-Query-Id")
        events = _get(conn, f"/debug/traces/{qid}")["traceEvents"]
        span = next(e for e in events if e["name"] == "map_reduce")
        assert span["args"]["mesh_devices"] == 8
        # and the cost tree of ?profile=1
        resp, data = _post(conn, "/index/i/query?profile=1", COUNT)
        assert json.loads(data)["profile"]["meshDevices"] == 8

    def test_a_host_served_read_and_a_write_carry_no_width(self, server):
        s, conn = server
        s.executor.use_mesh = False
        resp, data = _post(conn, "/index/i/query?trace=1", COUNT)
        assert json.loads(data)["results"] == [4]
        stats = json.loads(resp.getheader("X-Pilosa-Stats"))
        assert stats["devicePrograms"] == 0 and "meshDevices" not in stats
        qid = resp.getheader("X-Pilosa-Query-Id")
        events = _get(conn, f"/debug/traces/{qid}")["traceEvents"]
        span = next(e for e in events if e["name"] == "map_reduce")
        assert "mesh_devices" not in span["args"]
        resp, _ = _post(conn, "/index/i/query",
                        b'SetBit(frame="f", rowID=1, columnID=77)')
        assert "meshDevices" not in json.loads(
            resp.getheader("X-Pilosa-Stats"))
        assert _get(conn, "/debug/vars")["mesh"] is None

    def test_a_narrower_mesh_shows_in_every_answer(self, server):
        """What the benchmark's ``mesh_served_pct`` rests on: a server
        that meshed fewer devices than the host has says so a read."""
        s, conn = server
        s.executor._mesh = mesh_mod.make_mesh(4)
        resp, data = _post(conn, "/index/i/query", COUNT)
        assert json.loads(data)["results"] == [4]
        assert json.loads(resp.getheader("X-Pilosa-Stats"))[
            "meshDevices"] == 4
        got = _get(conn, "/debug/vars")
        assert got["mesh"]["shape"] == [1, 4]
        assert got["mesh"]["devices"] == 4
        assert sorted(got["deviceBlockCache"]["perDeviceBytes"]) == [
            "0", "1", "2", "3"]

    def test_a_make_mesh_failure_is_counted_and_served_by_the_host(
            self, server, monkeypatch):
        s, conn = server

        def no_backend(*a, **kw):
            raise RuntimeError("no device backend")
        monkeypatch.setattr(mesh_mod, "make_mesh", no_backend)
        for _ in range(2):      # the second is inside the backoff
            resp, data = _post(conn, "/index/i/query", COUNT)
            assert json.loads(data)["results"] == [4]
            stats = json.loads(resp.getheader("X-Pilosa-Stats"))
            assert stats["devicePrograms"] == 0
            assert "meshDevices" not in stats
        got = _get(conn, "/debug/vars")
        assert got["mesh"] == {"shape": None, "devices": 0, "builds": 0,
                               "failures": 1,
                               "programsRun": got["mesh"]["programsRun"]}
        assert got["deviceFallback"] == 1


# -- (e) nothing built for one width is used at another -----------------------

def test_a_slab_keyed_for_one_device_is_not_used_on_four(holder64):
    """``n_dev`` is the last part of every residency key: the same
    executor, handed a four-device mesh after serving on one device,
    reuses the route record (it names slices and fragments, no device)
    and none of the one-device slabs."""
    holder, bits = holder64
    rows = (1, 2, 4)
    pql = _pql(rows)
    ex = _executor(holder, 1)
    cache = residency.device_cache()
    hits0, misses0 = cache.hits, cache.misses

    def grown():
        return cache.hits - hits0, cache.misses - misses0
    try:
        for _ in range(2):
            got, stats = _served(ex, pql)
            assert got == _want(bits, rows) and stats["meshDevices"] == 1
        assert grown() == (3, 3)
        assert ex.route_memo["hits"] == 1
        one = list(cache._lru)
        assert all(k[-1] == 1 for k in one)
        ex._mesh = mesh_mod.make_mesh(4)
        got, stats = _served(ex, pql)
        assert got == _want(bits, rows) and stats["meshDevices"] == 4
        assert grown() == (3, 6)          # three new slabs
        assert ex.route_memo["hits"] == 2             # the record stands
        four = [k for k in cache._lru if k not in one]
        assert len(four) == 3 and all(k[-1] == 4 for k in four)
        assert sorted(k[:-1] for k in four) == sorted(k[:-1] for k in one)
        for k in four:
            assert len(cache._lru[k].addressable_shards) == 4
        leaves = [("f", VIEW_STANDARD, r) for r in rows]
        keys, found, cold = ex._leaf_lookup(ex._mesh, "i", leaves,
                                            list(range(64)))
        assert cold == 0 and sorted(keys) == sorted(four)
    finally:
        ex.close()
