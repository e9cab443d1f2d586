"""Bit-sliced integer fields (BSI): engine, schema, PQL, executor,
HTTP, and device legs.

The engine test is differential against a brute-force dict-of-ints
model over every operator and every predicate in (and beyond) the
domain; the executor test drives the full PQL → executor → storage
stack single-node; the generative test interleaves random value
writes/imports with Range/Sum/Min/Max queries against the model; the
kernel tests pin the XLA circuit to its numpy twin. The 2-node cluster
merge proof lives in test_bsi_cluster.py.
"""

import io
import json
import random

import numpy as np
import pytest

from pilosa_tpu import SLICE_WIDTH
from pilosa_tpu.errors import PilosaError
from pilosa_tpu.executor import Executor
from pilosa_tpu.models.frame import Field, Frame, FrameOptions
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.pql.ast import Condition
from pilosa_tpu.pql.parser import parse
from pilosa_tpu.storage import bsi
from pilosa_tpu.storage.bitmap import Bitmap


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "data"))
    h.open()
    yield h
    h.close()


@pytest.fixture
def executor(holder):
    ex = Executor(holder, host="local", use_mesh=False)
    yield ex
    ex.close()


def field_frame(holder, min_v=0, max_v=100, name="v"):
    idx = holder.create_index_if_not_exists("i")
    frame = idx.create_frame_if_not_exists("f")
    frame.create_field(Field(name, min_v, max_v))
    return frame


# -- engine vs brute force ----------------------------------------------------


class TestEngine:
    @pytest.mark.parametrize("mn,mx", [(0, 100), (-50, 37), (10, 10),
                                       (5, 6)])
    def test_all_ops_all_predicates_match_brute_force(self, mn, mx):
        rng = random.Random(7)
        depth = bsi.bit_depth(mn, mx)
        vals = {c: rng.randint(mn, mx) for c in range(80)
                if rng.random() < 0.7}
        planes = {bsi.EXISTS_PLANE: Bitmap(*vals.keys())}
        for i in range(depth):
            planes[i] = Bitmap(*[c for c, v in vals.items()
                                 if ((v - mn) >> i) & 1])

        def row(i):
            return planes[i]

        ops = {"<": lambda v, p: v < p, "<=": lambda v, p: v <= p,
               ">": lambda v, p: v > p, ">=": lambda v, p: v >= p,
               "==": lambda v, p: v == p, "!=": lambda v, p: v != p}
        for op, fn in ops.items():
            for p in range(mn - 3, mx + 4):
                got = bsi.range_bitmap(op, p, mn, mx, row)
                got_set = (set() if got is None
                           else set(got.bits().tolist()))
                want = {c for c, v in vals.items() if fn(v, p)}
                assert got_set == want, (op, p)
        for lo in range(mn - 2, mx + 3, 3):
            for hi in range(lo - 1, mx + 3, 3):
                got = bsi.range_bitmap("><", (lo, hi), mn, mx, row)
                got_set = (set() if got is None
                           else set(got.bits().tolist()))
                assert got_set == {c for c, v in vals.items()
                                   if lo <= v <= hi}, (lo, hi)

        sc = bsi.sum_count(mn, mx, row)
        assert (sc.value, sc.count) == (sum(vals.values()), len(vals))
        if vals:
            m = bsi.min_max(mn, mx, row, want_min=True)
            assert m.value == min(vals.values())
            assert m.count == sum(1 for v in vals.values()
                                  if v == m.value)
            m = bsi.min_max(mn, mx, row, want_min=False)
            assert m.value == max(vals.values())

    def test_combine_min_max_merge(self):
        a = bsi.ValCount(5, 2)
        b = bsi.ValCount(5, 3)
        assert bsi.combine_min_max(a, b).count == 5
        assert bsi.combine_min_max(a, bsi.ValCount(4, 1)).value == 4
        assert bsi.combine_min_max(
            a, bsi.ValCount(9, 1), want_min=False).value == 9
        # empty sides are identity
        assert bsi.combine_min_max(bsi.ValCount(0, 0), a) == a
        assert bsi.combine_min_max(a, bsi.ValCount(0, 0)) == a

    def test_depth_and_validation(self):
        assert bsi.bit_depth(0, 0) == 0
        assert bsi.bit_depth(0, 1) == 1
        assert bsi.bit_depth(-10, 100) == 7
        with pytest.raises(PilosaError):
            bsi.bit_depth(5, 4)
        with pytest.raises(PilosaError):
            Field("v", 0, 1 << 63)


# -- PQL conditions -----------------------------------------------------------


class TestConditionSyntax:
    @pytest.mark.parametrize("op", ["==", "!=", "<", "<=", ">", ">="])
    def test_roundtrip(self, op):
        q = parse(f'Range(frame="f", age {op} -7)')
        c = q.calls[0]
        assert c.args["age"] == Condition(op, -7)
        assert parse(str(c)).calls[0] == c

    def test_between_roundtrip(self):
        c = parse('Range(frame="f", v >< [3, 9])').calls[0]
        assert c.args["v"] == Condition("><", [3, 9])
        assert parse(str(c)).calls[0] == c

    def test_condition_arg_helper(self):
        c = parse('Range(frame="f", v > 2)').calls[0]
        assert c.condition_arg() == ("v", Condition(">", 2))
        assert parse('Bitmap(rowID=1)').calls[0].condition_arg() is None

    @pytest.mark.parametrize("bad", [
        'Range(frame="f", v >< 5)',
        'Range(frame="f", v >< [1])',
        'Range(frame="f", v > "x")',
        'Range(frame="f", v > 1.5)',
    ])
    def test_rejects_malformed(self, bad):
        with pytest.raises(PilosaError):
            parse(bad)

    def test_sum_form_parses(self):
        c = parse('Sum(Bitmap(rowID=1, frame="g"), frame="f",'
                  ' field="v")').calls[0]
        assert c.name == "Sum" and len(c.children) == 1
        assert c.args["field"] == "v"


# -- frame schema / writes ----------------------------------------------------


class TestFrameFields:
    def test_create_persist_reopen(self, tmp_path):
        f = Frame(str(tmp_path / "f"), "i", "f")
        f.open()
        f.create_field(Field("age", -10, 100))
        f.set_field_value("age", 5, 42)
        f.close()
        f2 = Frame(str(tmp_path / "f"), "i", "f")
        f2.open()
        assert f2.field("age") == Field("age", -10, 100)
        assert f2.field_value("age", 5) == (42, True)
        f2.close()

    def test_create_conflicting_range_rejected(self, tmp_path):
        f = Frame(str(tmp_path / "f"), "i", "f")
        f.open()
        f.create_field(Field("age", 0, 10))
        f.create_field(Field("age", 0, 10))  # idempotent
        with pytest.raises(PilosaError, match="different range"):
            f.create_field(Field("age", 0, 11))
        f.close()

    def test_set_value_overwrites_planes(self, tmp_path):
        f = Frame(str(tmp_path / "f"), "i", "f")
        f.open()
        f.create_field(Field("v", 0, 127))
        assert f.set_field_value("v", 1, 127)
        assert f.set_field_value("v", 1, 0)  # clears every 1-plane
        assert f.field_value("v", 1) == (0, True)
        assert not f.set_field_value("v", 1, 0)  # idempotent
        with pytest.raises(PilosaError, match="out of range"):
            f.set_field_value("v", 1, 128)
        f.close()

    def test_bulk_import_last_wins_and_overwrites(self, tmp_path):
        f = Frame(str(tmp_path / "f"), "i", "f")
        f.open()
        f.create_field(Field("v", -5, 50))
        f.import_field_values(
            "v", np.array([1, 2, 1, SLICE_WIDTH + 3], dtype=np.uint64),
            np.array([7, -5, 50, 12], dtype=np.int64))
        assert f.field_value("v", 1) == (50, True)  # last wins
        assert f.field_value("v", 2) == (-5, True)
        assert f.field_value("v", SLICE_WIDTH + 3) == (12, True)
        f.import_field_values("v", [1], [0])  # stale planes cleared
        assert f.field_value("v", 1) == (0, True)
        assert f.max_slice() == 1  # field views drive slice discovery
        with pytest.raises(PilosaError, match="out of range"):
            f.import_field_values("v", [9], [51])
        f.close()


# -- executor, single node ----------------------------------------------------


class TestExecutorBSI:
    def test_range_sum_min_max_end_to_end(self, holder, executor):
        field_frame(holder, 0, 100)
        vals = {3: 10, 5: 42, SLICE_WIDTH + 7: 42,
                2 * SLICE_WIDTH + 1: 99, 8: 0}
        for c, v in vals.items():
            r = executor.execute(
                "i", f'SetFieldValue(frame="f", columnID={c}, v={v})')
            assert r[0] is True
        assert executor.execute(
            "i", 'SetFieldValue(frame="f", columnID=3, v=10)')[0] is False

        res = executor.execute("i", 'Range(frame="f", v > 30)')[0]
        assert sorted(res.bits().tolist()) == sorted(
            c for c, v in vals.items() if v > 30)
        res = executor.execute("i", 'Range(frame="f", v == 42)')[0]
        assert sorted(res.bits().tolist()) == [5, SLICE_WIDTH + 7]
        res = executor.execute("i", 'Range(frame="f", v >< [10, 42])')[0]
        assert sorted(res.bits().tolist()) == [3, 5, SLICE_WIDTH + 7]
        assert executor.execute(
            "i", 'Count(Range(frame="f", v <= 10))')[0] == 2

        s = executor.execute("i", 'Sum(frame="f", field="v")')[0]
        assert (s.value, s.count) == (sum(vals.values()), len(vals))
        m = executor.execute("i", 'Min(frame="f", field="v")')[0]
        assert (m.value, m.count) == (0, 1)
        m = executor.execute("i", 'Max(frame="f", field="v")')[0]
        assert (m.value, m.count) == (99, 1)

    def test_filtered_aggregates_and_compose(self, holder, executor):
        frame = field_frame(holder, 0, 100)
        for c, v in {3: 10, 5: 42, 8: 0, 9: 77}.items():
            frame.set_field_value("v", c, v)
        for c in (3, 5, 8):
            executor.execute(
                "i", f'SetBit(frame="f", rowID=1, columnID={c})')
        s = executor.execute(
            "i", 'Sum(Bitmap(frame="f", rowID=1), frame="f",'
                 ' field="v")')[0]
        assert (s.value, s.count) == (52, 3)
        m = executor.execute(
            "i", 'Max(Bitmap(frame="f", rowID=1), frame="f",'
                 ' field="v")')[0]
        assert (m.value, m.count) == (42, 1)
        res = executor.execute(
            "i", 'Intersect(Range(frame="f", v >= 10),'
                 ' Bitmap(frame="f", rowID=1))')[0]
        assert sorted(res.bits().tolist()) == [3, 5]
        # a field Range inside Count inside Union
        n = executor.execute(
            "i", 'Count(Union(Range(frame="f", v == 0),'
                 ' Range(frame="f", v >= 77)))')[0]
        assert n == 2

    def test_errors(self, holder, executor):
        field_frame(holder, 0, 100)
        for bad, msg in [
            ('Range(frame="f", nope > 3)', "field not found"),
            ('Sum(frame="f", field="nope")', "field not found"),
            ('Sum(frame="f")', "field required"),
            ('SetFieldValue(frame="f", columnID=1, v=101)',
             "out of range"),
            ('SetFieldValue(frame="f", columnID=1)',
             "exactly one field"),
            ('SetFieldValue(columnID=1, v=3)', "frame required"),
        ]:
            with pytest.raises(PilosaError, match=msg):
                executor.execute("i", bad)

    def test_empty_and_all_clamps(self, holder, executor):
        frame = field_frame(holder, 10, 20)
        frame.set_field_value("v", 1, 15)
        assert executor.execute(
            "i", 'Count(Range(frame="f", v < 5))')[0] == 0
        assert executor.execute(
            "i", 'Count(Range(frame="f", v < 100))')[0] == 1
        assert executor.execute(
            "i", 'Count(Range(frame="f", v != 999))')[0] == 1
        s = executor.execute("i", 'Min(frame="f", field="v")')[0]
        assert (s.value, s.count) == (15, 1)

    def test_aggregate_on_empty_field(self, holder, executor):
        field_frame(holder, 0, 100)
        s = executor.execute("i", 'Sum(frame="f", field="v")')[0]
        assert (s.value, s.count) == (0, 0)
        m = executor.execute("i", 'Min(frame="f", field="v")')[0]
        assert m.count == 0


# -- generative differential vs dict-of-ints model ---------------------------


def test_differential_random_ops_match_model(holder):
    """Random SetFieldValue / bulk imports / overwrites interleaved
    with Range/Sum/Min/Max on a 3-slice domain must match a plain
    dict-of-ints model exactly at every step (satellite: BSI engine
    differential)."""
    ex = Executor(holder, host="local", use_mesh=False)
    mn, mx = -20, 200
    frame = field_frame(holder, mn, mx)
    rng = np.random.default_rng(42)
    model: dict[int, int] = {}
    n_cols = 3 * SLICE_WIDTH

    import operator
    op_fns = {"<": operator.lt, "<=": operator.le, ">": operator.gt,
              ">=": operator.ge, "==": operator.eq, "!=": operator.ne}

    def check(step):
        op = ("<", "<=", ">", ">=", "==", "!=")[
            int(rng.integers(0, 6))]
        p = int(rng.integers(mn - 5, mx + 6))
        got = ex.execute("i", f'Range(frame="f", v {op} {p})')[0]
        want = {c for c, v in model.items() if op_fns[op](v, p)}
        assert set(got.bits().tolist()) == want, (step, op, p)
        s = ex.execute("i", 'Sum(frame="f", field="v")')[0]
        assert (s.value, s.count) == (sum(model.values()), len(model)), step
        if model:
            m = ex.execute("i", 'Min(frame="f", field="v")')[0]
            assert m.value == min(model.values()), step
            m = ex.execute("i", 'Max(frame="f", field="v")')[0]
            assert m.value == max(model.values()), step

    for step in range(60):
        kind = int(rng.integers(0, 3))
        if kind == 0:  # point write (often overwriting)
            c = int(rng.integers(0, n_cols))
            v = int(rng.integers(mn, mx + 1))
            ex.execute(
                "i", f'SetFieldValue(frame="f", columnID={c}, v={v})')
            model[c] = v
        elif kind == 1:  # bulk import
            k = int(rng.integers(1, 120))
            cols = rng.integers(0, n_cols, k).astype(np.uint64)
            vals = rng.integers(mn, mx + 1, k).astype(np.int64)
            frame.import_field_values("v", cols, vals)
            for c, v in zip(cols.tolist(), vals.tolist()):
                model[c] = v
        else:
            check(step)
    check("final")
    ex.close()


# -- wire codec ---------------------------------------------------------------


class TestWire:
    def test_valcount_proto_roundtrip(self):
        from pilosa_tpu.server import codec
        resp = codec.encode_query_response(
            [bsi.ValCount(-7, 3), True, 5])
        from pilosa_tpu.proto import internal_pb2 as pb
        back = pb.QueryResponse.FromString(resp.SerializeToString())
        out = codec.decode_query_results(
            back, ["Sum", "SetFieldValue", "Count"])
        assert out == [bsi.ValCount(-7, 3), True, 5]

    def test_valcount_json(self):
        from pilosa_tpu.server import codec
        assert codec.result_to_json(bsi.ValCount(9, 2)) == {
            "value": 9, "count": 2}


# -- HTTP handler -------------------------------------------------------------


def wsgi_call(app, method, path, body=b"", content_type="", accept=""):
    qs = ""
    if "?" in path:
        path, _, qs = path.partition("?")
    environ = {"REQUEST_METHOD": method, "PATH_INFO": path,
               "QUERY_STRING": qs, "CONTENT_LENGTH": str(len(body)),
               "wsgi.input": io.BytesIO(body)}
    if content_type:
        environ["CONTENT_TYPE"] = content_type
    if accept:
        environ["HTTP_ACCEPT"] = accept
    out = {}

    def start_response(status, headers):
        out["status"] = int(status.split()[0])
    chunks = app(environ, start_response)
    return out["status"], b"".join(chunks)


class TestHandlerFields:
    @pytest.fixture
    def app(self, holder):
        from pilosa_tpu.server.handler import Handler
        ex = Executor(holder, host="local", use_mesh=False)
        yield Handler(holder, ex, host="local")
        ex.close()

    def test_field_lifecycle_over_http(self, app):
        assert wsgi_call(app, "POST", "/index/i", b"{}")[0] == 200
        body = json.dumps({"options": {"fields": [
            {"name": "qty", "min": 0, "max": 1000}]}}).encode()
        assert wsgi_call(app, "POST", "/index/i/frame/f", body)[0] == 200
        s, _ = wsgi_call(app, "POST", "/index/i/frame/f/field/price",
                         json.dumps({"min": -100, "max": 100}).encode())
        assert s == 200
        s, b = wsgi_call(app, "GET", "/index/i/frame/f/fields")
        assert json.loads(b)["fields"] == [
            {"name": "qty", "min": 0, "max": 1000},
            {"name": "price", "min": -100, "max": 100}]

        # JSON value import → query back over HTTP
        s, b = wsgi_call(
            app, "POST", "/index/i/frame/f/field/price/import",
            json.dumps({"columns": [1, 2, SLICE_WIDTH + 3],
                        "values": [-50, 10, 99]}).encode())
        assert s == 200, b
        s, b = wsgi_call(app, "POST", "/index/i/query",
                         b'Range(frame="f", price > 0)')
        assert json.loads(b)["results"][0]["bits"] == [2, SLICE_WIDTH + 3]
        s, b = wsgi_call(app, "POST", "/index/i/query",
                         b'Sum(frame="f", field="price")')
        assert json.loads(b)["results"][0] == {"value": 59, "count": 3}

        # protobuf import + protobuf query response
        from pilosa_tpu.proto import internal_pb2 as pb
        req = pb.ImportValueRequest(Index="i", Frame="f", Field="qty",
                                    Slice=0, ColumnIDs=[1, 2],
                                    Values=[5, 7])
        s, b = wsgi_call(app, "POST",
                         "/index/i/frame/f/field/qty/import",
                         req.SerializeToString(),
                         content_type="application/x-protobuf",
                         accept="application/x-protobuf")
        assert s == 200, b
        s, b = wsgi_call(app, "POST", "/index/i/query",
                         b'Max(frame="f", field="qty")',
                         accept="application/x-protobuf")
        resp = pb.QueryResponse.FromString(b)
        assert (resp.Results[0].ValCount.Val,
                resp.Results[0].ValCount.Count) == (7, 1)

        # schema surfaces the fields
        s, b = wsgi_call(app, "GET", "/schema")
        frames = json.loads(b)["indexes"][0]["frames"]
        assert {f["name"] for f in frames[0]["fields"]} == \
            {"qty", "price"}

    def test_field_error_statuses(self, app):
        wsgi_call(app, "POST", "/index/i", b"{}")
        wsgi_call(app, "POST", "/index/i/frame/f", b"{}")
        s, _ = wsgi_call(app, "POST", "/index/i/frame/f/field/b",
                         json.dumps({"min": 5, "max": 1}).encode())
        assert s == 400
        s, _ = wsgi_call(app, "POST", "/index/i/frame/f/field/b",
                         json.dumps({"bogus": 1}).encode())
        assert s == 400
        s, _ = wsgi_call(app, "POST",
                         "/index/i/frame/nope/field/x/import", b"{}")
        assert s == 404
        s, _ = wsgi_call(app, "POST",
                         "/index/i/frame/f/field/nope/import",
                         json.dumps({"columns": [1],
                                     "values": [1]}).encode())
        assert s == 404


# -- device kernels / mesh ----------------------------------------------------


class TestDeviceCircuit:
    def test_xla_circuit_matches_numpy_twin(self):
        import jax.numpy as jnp

        from pilosa_tpu.ops import kernels
        rng = np.random.default_rng(0)
        depth = 7
        planes = rng.integers(0, 2**32, size=(depth + 1, 2, 64),
                              dtype=np.uint32)
        planes[0] |= planes[1:].max(axis=0)  # exists ⊇ every plane
        for op in kernels.BSI_OPS:
            for upred in (0, 1, 37, 127):
                want = kernels.bsi_compare_words_host(op, upred, planes)
                got = np.asarray(kernels.bsi_compare_words(
                    op, kernels.bsi_predicate_bits(upred, depth),
                    jnp.asarray(planes)))
                assert (got == want).all(), (op, upred)

    def test_circuit_semantics_against_decoded_values(self):
        from pilosa_tpu.ops import kernels
        rng = np.random.default_rng(3)
        depth = 6
        planes = rng.integers(0, 2**32, size=(depth + 1, 1, 32),
                              dtype=np.uint32)
        planes[0] = 0xFFFFFFFF
        vals = np.zeros(32 * 32, dtype=np.int64)
        for i in range(depth):
            bits = np.unpackbits(planes[1 + i].view(np.uint8),
                                 bitorder="little")
            vals += bits.astype(np.int64) << i
        for op, fn in (("<", np.less), (">=", np.greater_equal),
                       ("==", np.equal)):
            got = kernels.bsi_compare_words_host(op, 21, planes)
            gotbits = np.unpackbits(got.view(np.uint8),
                                    bitorder="little").astype(bool)
            assert (gotbits == fn(vals, 21)).all(), op


class TestMeshBSI:
    def test_bsi_range_sharded_matches_host(self):
        from pilosa_tpu.ops import kernels
        from pilosa_tpu.parallel import mesh as mesh_mod
        mesh = mesh_mod.make_mesh(1)
        rng = np.random.default_rng(1)
        depth = 5
        n_slices, words = 4, 256
        planes = rng.integers(0, 2**32,
                              size=(depth + 1, n_slices, words),
                              dtype=np.uint32)
        planes[0] |= planes[1:].max(axis=0)
        arrs = [mesh_mod.shard_slices(mesh, planes[i])
                for i in range(depth + 1)]
        for op in ("<", ">=", "==", "!="):
            got = mesh_mod.bsi_range_sharded(mesh, op, 11, depth, arrs)
            want = kernels.bsi_compare_words_host(op, 11, planes)
            assert (got == want).all(), op
        got = mesh_mod.bsi_range_sharded(mesh, "><", (3, 19), depth,
                                         arrs)
        want = (kernels.bsi_compare_words_host(">=", 3, planes)
                & kernels.bsi_compare_words_host("<=", 19, planes))
        assert (got == want).all()

    def test_executor_device_legs_match_host(self, holder):
        """Acceptance (c): Range/Count/Sum through the mesh leg agree
        with the host path on the same data."""
        frame = field_frame(holder, -10, 50)
        rng = np.random.default_rng(5)
        cols = np.arange(0, 3 * SLICE_WIDTH, 401, dtype=np.uint64)
        vals = rng.integers(-10, 51, len(cols)).astype(np.int64)
        frame.import_field_values("v", cols, vals)
        host = Executor(holder, host="local", use_mesh=False)
        dev = Executor(holder, host="local", use_mesh=True,
                       mesh_min_slices=1)
        dev._cost_model_enabled = False
        try:
            for q in ('Range(frame="f", v > 17)',
                      'Count(Range(frame="f", v <= 0))',
                      'Sum(frame="f", field="v")',
                      'Sum(Range(frame="f", v >= 25), frame="f",'
                      ' field="v")'):
                got = dev.execute("i", q)[0]
                want = host.execute("i", q)[0]
                if hasattr(got, "bits"):
                    assert got.bits().tolist() == want.bits().tolist(), q
                else:
                    assert got == want, q
            assert dev.device_fallbacks == 0
        finally:
            host.close()
            dev.close()
