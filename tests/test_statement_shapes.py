"""A statement's shape is parsed and planned once (ISSUE 33).

``pql.parse`` lifts the integer argument values out of a text with one
regex pass and binds them into a memoised template; the planner keys
its entry by the literal-free call structure and binds a request's row
ids into the skeleton. Both halves are held to identity with the whole
pass they shorten: the parse differential against ``Parser(text)
.parse()`` (same Query, same ``str()``, same error text and position),
the plan differential against a planner that plans every request in
full, the invalidation matrix (no proof outlives what it proved), and
count guards on the warm path.
"""

import io
import json

import numpy as np
import pytest

from pilosa_tpu import SLICE_WIDTH
from pilosa_tpu.executor import ExecOptions, Executor
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.plan import planner as planner_mod
from pilosa_tpu.plan import record as plan_record
from pilosa_tpu.pql import parser as pql
from pilosa_tpu.pql import shape
from pilosa_tpu.pql.ast import Call, Condition, Query

N_ROWS = 8
N_SLICES = 3

# -- (a) parse differential -----------------------------------------------------

I63 = 1 << 63

VALID = [
    'Count(Intersect(Bitmap(frame="f", rowID=3), Bitmap(frame="f", rowID=17)))',
    'Count(Intersect(Bitmap(frame="f", rowID=1), Bitmap(frame="f", rowID=2),'
    ' Bitmap(frame="f", rowID=3), Bitmap(frame="f", rowID=4)))',
    "Count(Union(Bitmap(rowID=1, frame=f), Difference(Bitmap(rowID=2),"
    " Bitmap(rowID=3, frame=g))))",
    "TopN(Bitmap(rowID=5, frame=f), frame=f, n=10)",
    "TopN(Intersect(Bitmap(rowID=5), Bitmap(rowID=6)), frame=f, n=3,"
    " ids=[1, 2, 3])",
    "TopN(Bitmap(rowID=1), frame=f, ids=[7,-8, 9 ], field=\"x\","
    " filters=[\"a\", b, 3, true, false])",
    "Count(Bitmap(rowID=-1, frame=f))",
    "Count(Bitmap(rowID=-0, frame=f))",
    "Count(Bitmap(rowID=007, frame=f))",
    f"Count(Bitmap(rowID={I63 - 1}, frame=f))",
    f"Count(Bitmap(rowID=-{I63}, frame=f))",
    "Count(Bitmap(rowID=999999999999999999, frame=f))",      # 18 digits
    "Count(Bitmap(rowID=1000000000000000000, frame=f))",     # 19
    "Count(Bitmap(rowID=-999999999999999999, frame=f))",
    "Count(Bitmap(frame=f1, rowID=1))",                # digits in an ident
    'Count(Bitmap(frame="f1", rowID=1))',              # ... in a string
    'Count(Bitmap(frame="a=5", rowID=1))',             # a value in a string
    'Count(Bitmap(frame="a=6", rowID=1))',
    "Count(Bitmap(frame='x,7', rowID=1))",
    "Count(Bitmap(frame='x\"[9]', rowID=2))",          # the other quote
    'Count(Bitmap(frame="it\'s <3", rowID=2))',
    'Count(Bitmap(frame="q\\"=5", rowID=1))',          # escapes: full parser
    'Count(Bitmap(frame="q\\\\", rowID=1))',
    'Count(Bitmap(frame="l1\\nl2=3", rowID=1))',
    "Count(Bitmap(frame=a-5, rowID=1))",
    "Count(Bitmap(frame=a.5, rowID=1))",
    "Count(Range(frame=f, age > 20))",
    "Count(Range(frame=f, age>=20))",
    "Count(Range(frame=f, age == -3))",
    "Count(Range(frame=f, age != 0))",
    "Count(Range(frame=f, age < 7))",
    "Count(Range(frame=f, age <= 7))",
    "Count(Range(frame=f, age >< [20, 30]))",
    "Count(Range(frame=f, age ><[ -5,5 ]))",
    "Sum(Range(frame=f, age > 20), frame=f, field=age)",
    'Range(rowID=1, frame=f, start="2017-01-01T00:00", end="2018-01-01T00:00")',
    "SetRowAttrs(frame=f, rowID=1, x=1.5, y=-2.25, z=-.5, w=3., ok=true,"
    " no=false, nil=null, name=\"n\")",
    "SetColumnAttrs(columnID=9, x=2.5, k=7)",
    "SetFieldValue(frame=f, columnID=3, age=40)",
    "Count(  Intersect(\n\tBitmap( rowID = 3 , frame = f ) ,\n"
    "  Bitmap(rowID=\n4, frame=f)  )\n)",
    "Count(Bitmap(rowID=1))\nCount(Bitmap(rowID=2))  Count(Bitmap(rowID=3))",
    "Count(Bitmap(frame=f, rowID=1), n=2)",
    "Count(Bitmap(ids=[1, null, x]))",     # idents in a list are names
    "Count(Bitmap(rowID=1, frame=f), Bitmap(rowID=2, frame=f))",
    "",
    "   \n ",
]

INVALID = [
    "Count(Bitmap(rowID=1, rowID=2))",                  # duplicate key
    "Count(Intersect(Bitmap(rowID=1), Bitmap(rowID=2, rowID=3)))",
    'Count(Bitmap(frame="f, rowID=1))',                 # unterminated
    "Count(Bitmap(frame='f, rowID=1))",
    'Count(Bitmap(frame="f\nx", rowID=1))',             # newline in string
    'Count(Bitmap(frame="f\\q", rowID=1))',             # bad escape
    f"Count(Bitmap(rowID={I63}, frame=f))",             # past int64
    f"Count(Bitmap(rowID=-{I63 + 1}, frame=f))",
    "Count(Bitmap(rowID=12345678901234567890123))",
    "Count(Bitmap(rowID=, frame=f))",
    "Count(Bitmap(rowID=?, frame=f))",
    "Count(Intersect(Bitmap(rowID=?), Bitmap(rowID=3 4)))",
    "Count(Intersect(Bitmap(rowID=, frame=f), Bitmap(rowID=3 4, frame=f)))",
    "Count(Bitmap(rowID=3 4, frame=f))",
    "Count(Bitmap(rowID=5-3))",
    "Count(Bitmap(rowID=--5))",
    "Count(Bitmap(rowID=-))",
    "Count(Bitmap(rowID=5abc))",
    "Count(Bitmap(rowID=1.2.3))",
    "Count(Bitmap(rowID=1, 5))",
    "Count(Bitmap(rowID=[]))",
    "Count(Bitmap(ids=[1, [2]]))",
    "Count(Bitmap(ids=[1, 2.5]))",
    "Count(Range(frame=f, age > 2.5))",
    "Count(Range(frame=f, age >< [1]))",
    "Count(Range(frame=f, age >< 5))",
    "Count(Range(frame=f, age > [1, 2]))",
    "Count(Bitmap(rowID=1)",
    "Count(Bitmap(rowID=1)))",
    "Count Bitmap(rowID=1)",
    "Bitmap (rowID=1)  x",
    "5",
    "Count(Bitmap(rowID=1) Bitmap(rowID=2))",
]


def _full(text):
    """(Query, None) or (None, error text) of the whole grammar."""
    try:
        return pql.Parser(text).parse(), None
    except pql.ParseError as e:
        return None, (str(e), e.pos)


def _shaped(text):
    try:
        return pql.parse(text), None
    except pql.ParseError as e:
        return None, (str(e), e.pos)


def _deep_types(call):
    """Argument values with their types: ``true`` must not come back
    as ``1`` nor ``3.`` as ``3`` (they compare equal)."""
    out = [(k, type(v).__name__,
            [type(x).__name__ for x in v] if isinstance(v, list)
            else type(v.value).__name__ if isinstance(v, Condition)
            else None) for k, v in call.args.items()]
    return (call.name, out, [_deep_types(c) for c in call.children])


@pytest.fixture
def fresh_shapes():
    saved = dict(pql._shapes)
    pql._shapes.clear()
    yield pql._shapes
    pql._shapes.clear()
    pql._shapes.update(saved)


class TestParseDifferential:
    @pytest.mark.parametrize("text", VALID)
    def test_valid_text_parses_as_the_grammar_does(self, text,
                                                   fresh_shapes):
        want, err = _full(text)
        assert err is None, err
        for _ in range(3):   # first sighting, then from the template
            got, gerr = _shaped(text)
            assert gerr is None
            assert got == want
            assert str(got) == str(want)
            assert [_deep_types(c) for c in got.calls] == \
                [_deep_types(c) for c in want.calls]

    @pytest.mark.parametrize("text", INVALID)
    def test_invalid_text_fails_as_the_grammar_does(self, text,
                                                    fresh_shapes):
        want, err = _full(text)
        assert want is None, text
        for _ in range(2):
            assert _shaped(text) == (None, err)
        assert not fresh_shapes      # an error is never a template

    def test_an_error_after_its_valid_twin_is_still_the_grammars(
            self, fresh_shapes):
        """The dangerous order: templates of every valid text are
        warm, then the invalid ones arrive and must not bind into a
        neighbour's shape."""
        for text in VALID:
            pql.parse(text)
        for text in INVALID:
            assert _shaped(text) == (None, _full(text)[1]), text
        for text in VALID:
            assert pql.parse(text) == _full(text)[0], text

    def test_other_integers_bind_into_a_known_shape(self, fresh_shapes):
        rng = np.random.default_rng(33)
        base = ('Count(Intersect(Bitmap(frame="f", rowID={}),'
                ' Bitmap(frame="f", rowID={}), Bitmap(frame="f",'
                ' rowID={})))\nTopN(Bitmap(rowID={}), frame=f, n={},'
                ' ids=[{}, {}])\nCount(Range(frame=f, age >< [{}, {}]))')
        pql.parse(base.format(*range(9)))
        assert len(fresh_shapes) == 1
        edges = [0, 1, -1, I63 - 1, -I63, 10 ** 18 - 1, -(10 ** 18) + 1]
        for _ in range(50):
            vals = [int(rng.choice(edges)) if rng.random() < 0.3
                    else int(rng.integers(-10 ** 6, 10 ** 6))
                    for _ in range(9)]
            text = base.format(*vals)
            assert pql.parse(text) == pql.Parser(text).parse()
        # values past 18 digits are not parameters: their own entries
        assert 1 <= len(fresh_shapes) <= 51

    def test_a_bound_query_shares_nothing_mutable_with_its_shape(
            self, fresh_shapes):
        text = "TopN(Bitmap(rowID=1), frame=f, ids=[1, 2], tags=[a, b])"
        first = pql.parse(text)
        a = pql.parse(text)
        a.calls[0].args["ids"].append(99)
        a.calls[0].args["tags"].append("c")
        a.calls[0].children[0].args["rowID"] = 7
        a.calls[0].children.clear()
        assert pql.parse(text) == first == pql.Parser(text).parse()

    def test_the_memo_is_bounded_and_long_texts_stay_out(
            self, fresh_shapes):
        for i in range(pql._SHAPE_ENTRIES + 40):
            pql.parse(f"Count(Bitmap(rowID=1, frame=f{i}))")
        assert len(fresh_shapes) == pql._SHAPE_ENTRIES
        full = pql.shape_stats["full"]
        long = "Count(Union(%s))" % ", ".join(
            f"Bitmap(rowID={i})" for i in range(400))
        assert len(long) > pql._SHAPE_TEXT_MAX
        assert pql.parse(long) == pql.Parser(long).parse()
        assert pql.shape_stats["full"] == full + 1
        assert len(fresh_shapes) == pql._SHAPE_ENTRIES

    def test_flat_calls_keep_their_own_lane(self, fresh_shapes):
        for text in ('SetBit(frame="f", rowID=1, columnID=2)',
                     'TopN(frame="f", n=4, ids=[1, 2])',
                     'SetBit(frame="f", rowID=1, columnID=2)'
                     ' ClearBit(frame="f", rowID=1, columnID=3)'):
            assert pql.parse(text) == pql.Parser(text).parse()
        assert not fresh_shapes


class TestShapeModule:
    def test_lift_spec_and_bind_agree_on_slot_order(self):
        call = Call("TopN", {"n": 5, "ids": [7, "x", 8], "flag": True,
                             "cond": Condition("><", [1, 2]),
                             "f": 1.0, "frame": "f"},
                    [Call("Bitmap", {"rowID": 3, "frame": "f"}),
                     Call("Bitmap", {"columnID": 4})])
        vals: list = []
        key = shape.lift(call, vals)
        assert vals == [3, 4, 5, 7, 8, 1, 2]
        found: list = []
        shape.ints_of(call, found)
        assert found == vals
        spec, n = shape.spec_of(call)
        assert n == len(vals)
        assert shape.bind_call(spec, vals) == call
        other = [30, 40, 50, 70, 80, 10, 20]
        bound = shape.bind_call(spec, other)
        again: list = []
        assert shape.lift(bound, again) == key and again == other
        assert bound.args["flag"] is True and bound.args["f"] == 1.0
        assert bound.args["ids"] == [70, "x", 80]
        assert bound.args["cond"] == Condition("><", [10, 20])

    def test_equal_values_of_other_types_are_other_shapes(self):
        keys = {shape.lift(Call("C", {"x": v}), [])
                for v in (True, 1.0, "1", None, False, 0.0)}
        assert len(keys) == 6
        assert shape.lift(Call("C", {"x": 1}), []) == \
            shape.lift(Call("C", {"x": 0}), [])

    def test_an_unhashable_value_raises_where_it_is_looked_up(self):
        key = shape.lift(Call("C", {"x": {"a": 1}}), [])
        with pytest.raises(TypeError):
            hash(key)


# -- fixtures -------------------------------------------------------------------


def _load(holder, rng, n_slices=N_SLICES):
    """Frames with skewed rows (f), sparse rows (g: rows past N_ROWS
    are absent), a frame with no view (h); ``nope`` does not exist."""
    idx = holder.create_index("p")
    f = idx.create_frame("f")
    for row in range(N_ROWS):
        k = max(4, 4000 >> row)
        cols = rng.choice(n_slices * SLICE_WIDTH, size=k, replace=False)
        f.import_bits(np.full(k, row, dtype=np.uint64),
                      cols.astype(np.uint64))
    g = idx.create_frame("g")
    for row in range(0, N_ROWS, 2):
        cols = rng.choice(n_slices * SLICE_WIDTH, size=3 * (row + 1),
                          replace=False)
        g.import_bits(np.full(len(cols), row, dtype=np.uint64),
                      cols.astype(np.uint64))
    idx.create_frame("h")
    return idx


@pytest.fixture
def holder(tmp_path):
    h = Holder(str(tmp_path / "data"))
    h.open()
    _load(h, np.random.default_rng(7))
    yield h
    h.close()


def _rand_tree(rng, depth):
    if depth == 0 or rng.random() < 0.35:
        frame = rng.choice(["f", "f", "g", "h", "nope"])
        row = int(rng.integers(N_ROWS + 3))
        form = rng.random()
        if form < 0.1:
            return f"Bitmap(columnID={row}, frame={frame})"
        if form < 0.2:
            return f"Bitmap(rowID={row})"
        return f'Bitmap(rowID={row}, frame="{frame}")'
    op = rng.choice(["Intersect", "Union", "Difference"])
    k = int(rng.integers(0 if rng.random() < 0.05 else 1, 5))
    return (f"{op}(" + ", ".join(_rand_tree(rng, depth - 1)
                                 for _ in range(k)) + ")")


def _rand_read(rng, frames=("f", "g")):
    """A read every frame of which exists (it must also execute)."""
    def tree(depth):
        if depth == 0 or rng.random() < 0.35:
            return (f'Bitmap(rowID={int(rng.integers(N_ROWS + 3))},'
                    f' frame="{rng.choice(frames)}")')
        op = rng.choice(["Intersect", "Union", "Difference"])
        return (f"{op}(" + ", ".join(
            tree(depth - 1) for _ in range(int(rng.integers(1, 5))))
            + ")")
    return f"Count({tree(int(rng.integers(1, 4)))})"


def _node_fields(call):
    """Every planned call with the fields of its plan node that the
    executor or an operator reads."""
    n = call._plan_node
    out = {"call": call.name, "args": sorted(call.args.items()),
           "op": n.op, "detail": n.detail, "est": n.est_rows,
           "exact": bool(n.exact), "cost": n.est_cost_s,
           "placement": n.placement, "decisions": list(n.decisions),
           "frames": sorted(n.frames), "lookup": n.cache_lookup,
           "store": n.cache_store, "short_circuit": n.short_circuit,
           "key": n.key if n.cache_lookup else None}
    assert len(call.children) == len(n.children) or n.short_circuit
    out["children"] = [_node_fields(c) for c in call.children]
    return out


def _norm(results):
    return [list(r.bits()) if hasattr(r, "bits") else r
            for r in results]


# -- (b) plan differential ------------------------------------------------------


class TestPlanDifferential:
    @pytest.mark.parametrize("seed,n_slices", [
        (41, N_SLICES), (42, N_SLICES),
        (43, planner_mod.EXACT_SLICES + 6)])
    def test_shape_path_plans_what_a_full_pass_plans(self, tmp_path,
                                                     seed, n_slices):
        """Two planners over one holder see one sequence of requests
        (repeats included, so the CSE ladder climbs, and writes
        between, so tokens move): one binds shape entries, the other
        plans every request in full. Field by field the same plan."""
        from pilosa_tpu.parallel.costmodel import Calibration
        rng = np.random.default_rng(seed)
        h = Holder(str(tmp_path / "d"))
        h.open()
        try:
            _load(h, rng, n_slices)
            shaped = planner_mod.Planner(h)
            whole = planner_mod.Planner(h)
            shaped.calibration = whole.calibration = Calibration(
                1e-3, 12e9, 150e-6, 6e9, 2e8, 7e11)
            slices = list(range(n_slices))
            texts = []
            for _ in range(70):
                t = _rand_tree(rng, int(rng.integers(1, 4)))
                wrap = rng.random()
                texts.append(
                    f"Count({t})" if wrap < 0.5 else
                    f"TopN({t}, frame=f, n={int(rng.integers(1, 6))})"
                    if wrap < 0.65 else
                    f"Count({t}) Count({_rand_tree(rng, 1)})"
                    if wrap < 0.75 else t)
            for _ in range(60):     # few shapes, many rows
                rows = [int(r) for r in rng.integers(N_ROWS + 3, size=4)]
                texts.append(str(rng.choice([
                    'Count(Intersect(Bitmap(frame="f", rowID={}),'
                    ' Bitmap(frame="f", rowID={}), Bitmap(frame="g",'
                    ' rowID={})))',
                    "Count(Union(Bitmap(rowID={}, frame=g), Difference("
                    "Bitmap(rowID={}, frame=f), Bitmap(rowID={}, frame=g),"
                    " Bitmap(rowID={}, frame=h))))",
                    "Union(Bitmap(rowID={}, frame=g),"
                    " Bitmap(rowID={}, frame=g))"])).format(*rows))
            texts += texts[:30]
            ex = Executor(h, host="local", use_mesh=False)
            for i, text in enumerate(texts):
                if i % 25 == 24:
                    frame = rng.choice(["f", "g"])
                    ex.execute("p", f"SetBit(frame={frame}, rowID="
                               f"{int(rng.integers(N_ROWS + 3))}, columnID="
                               f"{int(rng.integers(n_slices * SLICE_WIDTH))})")
                calls = pql.parse(text).calls
                for all_local in (True, False):
                    got, grec = shaped.plan_query_cached(
                        "p", calls, slices, all_local=all_local,
                        slices_key=("k", n_slices))
                    want, wrec = whole.plan_query(
                        "p", calls, slices, all_local=all_local)
                    assert [_node_fields(c) for c in got] == \
                        [_node_fields(c) for c in want], text
                    assert [str(c) for c in got] == \
                        [str(c) for c in want]
                    assert grec.fingerprint == wrec.fingerprint
                    assert grec.decision_summary() == \
                        wrec.decision_summary()
                    assert grec.to_tree() == wrec.to_tree()
                    # the request's own calls are never the planned ones
                    assert calls == pql.Parser(text).parse().calls
            assert shaped.shapes["hits"] > 150
            assert shaped.shapes["misses"] > 50
            assert shaped.shapes["full"] == 0
            assert whole.shapes == {"hits": 0, "misses": 0,
                                    "full": 2 * len(texts)}
        finally:
            h.close()

    @pytest.mark.parametrize("seed", [51, 52])
    def test_host_and_device_answers_are_the_planner_off_answers(
            self, tmp_path, seed):
        rng = np.random.default_rng(seed)
        h = Holder(str(tmp_path / "d"))
        h.open()
        try:
            _load(h, rng)
            host = Executor(h, host="local", use_mesh=False)
            device = Executor(h, host="local", use_mesh=True,
                              mesh_min_slices=1)
            off = Executor(h, host="local", use_mesh=False)
            off.planner_enabled = False
            n_cols = N_SLICES * SLICE_WIDTH
            for step in range(40):
                if rng.random() < 0.25:
                    host.execute(
                        "p", f"SetBit(frame={rng.choice(['f', 'g'])},"
                             f" rowID={int(rng.integers(N_ROWS + 3))},"
                             f" columnID={int(rng.integers(n_cols))})")
                    continue
                q = _rand_read(rng)
                want = _norm(off.execute("p", q))
                assert _norm(host.execute("p", q)) == want, (step, q)
                assert _norm(device.execute("p", q)) == want, (step, q)
            assert host.planner.shapes["hits"] > 0
            assert device.planner.shapes["hits"] > 0
            device.close()
        finally:
            h.close()


# -- (c) invalidation matrix ----------------------------------------------------


def _q(frame_a, row_a, frame_b, row_b):
    return (f"Count(Intersect(Bitmap(rowID={row_a}, frame={frame_a}),"
            f" Bitmap(rowID={row_b}, frame={frame_b})))")


class TestInvalidation:
    """Each change re-estimates: the very next answer is the new one,
    from a shape entry that was warm and a proof that was standing."""

    def _warm(self, ex, q, want):
        for _ in range(3):
            ex._bitmap_results.clear()
            assert ex.execute("p", q)[0] == want

    def test_setbit_into_a_proven_empty_row(self, holder):
        ex = Executor(holder, host="local", use_mesh=False)
        empty_row = N_ROWS + 1
        col = int(ex.execute("p", "Bitmap(rowID=0, frame=f)")[0].bits()[0])
        q = _q("f", 0, "f", empty_row)
        self._warm(ex, q, 0)
        node = ex._maybe_plan("p", pql.parse(q), list(range(N_SLICES)),
                              ExecOptions())[0].calls[0]._plan_node
        assert node.short_circuit      # the proof stood
        ex.execute("p", f"SetBit(frame=f, rowID={empty_row},"
                        f" columnID={col})")
        ex._bitmap_results.clear()
        assert ex.execute("p", q)[0] == 1
        ex.execute("p", f"ClearBit(frame=f, rowID={empty_row},"
                        f" columnID={col})")
        ex._bitmap_results.clear()
        assert ex.execute("p", q)[0] == 0

    def test_a_view_appearing(self, holder):
        ex = Executor(holder, host="local", use_mesh=False)
        col = int(ex.execute("p", "Bitmap(rowID=0, frame=f)")[0].bits()[0])
        q = _q("f", 0, "h", 2)           # h has no standard view
        self._warm(ex, q, 0)
        misses = ex.planner.shapes["misses"]
        ex.execute("p", f"SetBit(frame=h, rowID=2, columnID={col})")
        ex._bitmap_results.clear()
        assert ex.execute("p", q)[0] == 1
        assert ex.planner.shapes["misses"] == misses + 1   # rebuilt

    def test_a_fragment_appearing(self, tmp_path):
        h = Holder(str(tmp_path / "d"))
        h.open()
        try:
            f = h.create_index("p").create_frame("f")
            f.import_bits(np.zeros(4, dtype=np.uint64),
                          np.arange(4, dtype=np.uint64))
            col = 2 * SLICE_WIDTH + 1    # a slice with no fragment
            f.import_bits(np.array([0], dtype=np.uint64),
                          np.array([col], dtype=np.uint64))
            f.import_bits(np.array([1], dtype=np.uint64),
                          np.array([0], dtype=np.uint64))
            ex = Executor(h, host="local", use_mesh=False)
            q = _q("f", 0, "f", 5)
            self._warm(ex, q, 0)
            # row 5's first bit lands in slice 1, which has no
            # fragment yet: the fragment appears with it
            ex.execute("p", f"SetBit(frame=f, rowID=5,"
                            f" columnID={SLICE_WIDTH + 9})")
            ex.execute("p", f"SetBit(frame=f, rowID=0,"
                            f" columnID={SLICE_WIDTH + 9})")
            ex._bitmap_results.clear()
            assert ex.execute("p", q)[0] == 1
        finally:
            h.close()

    def test_a_frame_dropped_and_recreated(self, holder):
        ex = Executor(holder, host="local", use_mesh=False)
        idx = holder.index("p")
        q = _q("f", 0, "g", 0)
        before = ex.execute("p", q)[0]
        self._warm(ex, q, before)
        cols = ex.execute("p", "Bitmap(rowID=0, frame=f)")[0].bits()[:5]
        idx.delete_frame("g")
        g = idx.create_frame("g")
        ex._bitmap_results.clear()
        assert ex.execute("p", q)[0] == 0        # the new g is empty
        g.import_bits(np.zeros(5, dtype=np.uint64),
                      np.asarray(cols, dtype=np.uint64))
        ex._bitmap_results.clear()
        assert ex.execute("p", q)[0] == 5

    def test_a_frame_created_after_its_first_asking(self, holder):
        ex = Executor(holder, host="local", use_mesh=False)
        q = "Count(Bitmap(rowID=1, frame=late))"
        for _ in range(2):
            with pytest.raises(Exception):
                ex.execute("p", q)
        late = holder.index("p").create_frame("late")
        late.import_bits(np.ones(3, dtype=np.uint64),
                         np.arange(3, dtype=np.uint64))
        assert ex.execute("p", q)[0] == 3
        node = ex._maybe_plan("p", pql.parse(q), list(range(N_SLICES)),
                              ExecOptions())[0].calls[0]._plan_node
        assert node.est_rows == 3 and node.exact

    def test_an_import(self, holder):
        ex = Executor(holder, host="local", use_mesh=False)
        empty_row = N_ROWS + 2
        q = _q("f", 1, "f", empty_row)
        self._warm(ex, q, 0)
        cols = ex.execute("p", "Bitmap(rowID=1, frame=f)")[0].bits()[:7]
        holder.index("p").frame("f").import_bits(
            np.full(7, empty_row, dtype=np.uint64),
            np.asarray(cols, dtype=np.uint64))
        ex._bitmap_results.clear()
        assert ex.execute("p", q)[0] == 7

    def test_the_row_memo_is_bounded(self, holder):
        ex = Executor(holder, host="local", use_mesh=False)
        slices = list(range(N_SLICES))
        cap = planner_mod._ESTIMATE_CACHE_ENTRIES
        calls = pql.parse("Count(Bitmap(rowID=0, frame=f))").calls
        ex.planner.plan_query_cached("p", calls, slices)
        for row in range(cap + 50):
            calls[0].children[0].args["rowID"] = row
            ex.planner.plan_query_cached("p", calls, slices)
        assert len(ex.planner._rows) == cap


# -- (d) count guards on the warm path ------------------------------------------


class _CountingLock:
    def __init__(self):
        import threading
        self._mu = threading.Lock()
        self.taken = 0

    def __enter__(self):
        self._mu.acquire()
        self.taken += 1
        return self

    def __exit__(self, *exc):
        self._mu.release()
        return False


def _call(app, method, path, body=b""):
    path, _, qs = path.partition("?")
    environ = {"REQUEST_METHOD": method, "PATH_INFO": path,
               "QUERY_STRING": qs, "CONTENT_LENGTH": str(len(body)),
               "wsgi.input": io.BytesIO(body)}
    out = {}

    def start_response(status, headers):
        out["status"] = int(status.split()[0])

    chunks = app(environ, start_response)
    return out["status"], json.loads(b"".join(chunks))


@pytest.fixture
def served(holder):
    from pilosa_tpu.sched import QueryRegistry
    from pilosa_tpu.server.handler import Handler
    ex = Executor(holder, host="local", use_mesh=False)
    handler = Handler(holder, ex, host="local",
                      registry=QueryRegistry())
    yield handler, ex


def _hot(rows):
    return ("Count(Intersect(%s))" % ", ".join(
        f'Bitmap(frame="f", rowID={r})' for r in rows)).encode()


def _plan_shapes(handler):
    return _call(handler, "GET", "/debug/vars")[1]["planShapes"]


class TestWarmShapeCounts:
    @pytest.mark.parametrize("k", [2, 3, 4])
    def test_a_warm_shape_scans_nothing_and_fingerprints_nothing(
            self, served, monkeypatch, k):
        handler, ex = served
        off = Executor(ex.holder, host="local", use_mesh=False)
        off.planner_enabled = False
        for first in range(N_ROWS - k + 1):     # the shape, every row
            assert _call(handler, "POST", "/index/p/query",
                         _hot(range(first, first + k)))[0] == 200
        counts = {"scan": 0, "fingerprint": 0}
        scan = pql.Scanner.scan

        def counting_scan(self):
            counts["scan"] += 1
            return scan(self)

        def counting_fingerprint(calls):
            counts["fingerprint"] += 1
            return plan_record.fingerprint_calls(calls)

        monkeypatch.setattr(pql.Scanner, "scan", counting_scan)
        monkeypatch.setattr(planner_mod, "fingerprint_calls",
                            counting_fingerprint)
        before = _plan_shapes(handler)
        lock = ex.planner._mu = _CountingLock()
        rows = list(range(k))[::-1]             # not asked together yet
        status, doc = _call(handler, "POST", "/index/p/query", _hot(rows))
        assert status == 200
        assert doc["results"] == off.execute("p", _hot(rows).decode())
        assert counts == {"scan": 0, "fingerprint": 0}
        assert 0 < lock.taken <= 2, lock.taken
        after = _plan_shapes(handler)
        assert after == {"hits": before["hits"] + 1,
                         "misses": before["misses"],
                         "full": before["full"]}

    def test_profile_and_plan_requests_take_the_whole_planner(
            self, served):
        handler, ex = served
        off = Executor(ex.holder, host="local", use_mesh=False)
        off.planner_enabled = False
        q = _hot([0, 1, 2])
        assert _call(handler, "POST", "/index/p/query", q)[0] == 200
        before = _plan_shapes(handler)
        status, doc = _call(handler, "POST",
                            "/index/p/query?profile=1", q)
        assert status == 200
        assert doc["results"] == off.execute("p", q.decode())
        root = doc["plan"]["calls"][0]
        assert root["actualRows"] == doc["results"][0]
        assert "actualS" in root
        after = _plan_shapes(handler)
        assert after == {"hits": before["hits"],
                         "misses": before["misses"],
                         "full": before["full"] + 1}
        status, doc = _call(handler, "POST", "/index/p/query?plan=1", q)
        assert status == 200 and doc["results"] == []
        assert _plan_shapes(handler)["full"] == before["full"] + 2

    def test_a_text_that_cannot_be_parameterised_counts_as_full(
            self, served):
        handler, ex = served
        off = Executor(ex.holder, host="local", use_mesh=False)
        off.planner_enabled = False
        # An escape in a string: the shape pass does not model it.
        q = (b'Count(Intersect(Bitmap(frame="f", rowID=0),'
             b' Bitmap(frame="f", rowID=1, note="a\\"=5")))')
        want = off.execute("p", _hot([0, 1]).decode())
        before = _plan_shapes(handler)
        for n in (1, 2):
            status, doc = _call(handler, "POST", "/index/p/query", q)
            assert status == 200, doc
            assert doc["results"] == want
            assert _plan_shapes(handler)["full"] == before["full"] + n

    def test_a_shape_with_an_unhashable_value_is_planned_in_full(
            self, holder):
        ex = Executor(holder, host="local", use_mesh=False)
        off = Executor(holder, host="local", use_mesh=False)
        off.planner_enabled = False
        q = pql.parse("Count(Union(Bitmap(rowID=0, frame=f),"
                      " Bitmap(rowID=1, frame=f)))")
        q.calls[0].args["hint"] = {"not": "hashable"}
        want = off.execute("p", Query([c.clone() for c in q.calls]))
        for n in (1, 2):
            assert ex.execute("p", Query([c.clone() for c in q.calls])) \
                == want
            assert ex.planner.shapes == {"hits": 0, "misses": 0,
                                         "full": n}

    def test_every_sixteenth_hit_records_in_full(self, served):
        handler, ex = served
        for i in range(1 + 32):
            _call(handler, "POST", "/index/p/query",
                  _hot([i % N_ROWS, (i + 1) % N_ROWS]))
        doc = _call(handler, "GET", "/debug/plans")[1]
        assert doc["plans"][0]["count"] == 3    # first, 16th, 32nd hit


# -- the memos under many threads ------------------------------------------------


def test_many_threads_share_the_memos_without_mixing_requests(
        holder, monkeypatch, fresh_shapes):
    """More threads than cores, a short switch interval, memos small
    enough to evict all the time: every request still gets ITS rows
    planned (a lost update or a torn entry would hand one thread
    another's), and the memos keep their bounds."""
    import sys
    import threading
    monkeypatch.setattr(planner_mod, "_ESTIMATE_CACHE_ENTRIES", 8)
    monkeypatch.setattr(pql, "_SHAPE_ENTRIES", 4)
    ex = Executor(holder, host="local", use_mesh=False)
    slices = list(range(N_SLICES))
    shapes = ['Count(Intersect(%s))' % ", ".join(
        ['Bitmap(frame="%s", rowID={})' % f for f in frames])
        for frames in ("ff", "fg", "gf", "fff", "fgf", "ffgg")]
    errors: list = []

    def worker(seed):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(150):
                text = str(rng.choice(shapes))
                rows = [int(r) for r in
                        rng.integers(N_ROWS + 3, size=text.count("{}"))]
                query = pql.parse(text.format(*rows))
                planned, rec = ex.planner.plan_query_cached(
                    "p", query.calls, slices)
                leaves = (planned[0].children[0].children
                          or query.calls[0].children[0].children)
                got = sorted(c.args["rowID"] for c in leaves)
                assert got == sorted(rows), (text, rows, got)
                ests = [c._plan_node.est_rows for c in leaves]
                if not planned[0].children[0]._plan_node.short_circuit:
                    assert ests == sorted(ests)
                assert rec.roots[0].op == "Count"
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(s,))
                   for s in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert not errors, errors[:3]
    assert len(ex.planner._rows) <= 8
    assert len(pql._shapes) <= 4
    stats = ex.planner.shapes
    assert stats["full"] == 0 and stats["hits"] > stats["misses"] > 0
