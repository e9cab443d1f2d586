"""The benchmark's identity, and the TopN cell's template and reference
through the harness, in tier-1.

(a) The harness draws, loads and judges what it did before templates
    were modules: copied from ``cellbench/tests/test_templates.py`` with
    its constants as they stand (taken on the tree before that change,
    by the same code), every cell's requests, bits and verdicts have to
    come out the same, seed for seed.
(b) The cell ``c3-topn1000-src`` (configuration ``baseline-c3``,
    reference ``refs/topn_sparse.py``, template ``templates/topn_src.py``)
    runs whole through ``run_cell.run_resolved`` on the CPU at 2 slices,
    its server child taking the device path (``tests/rw95_child.py``):
    ``correct`` true, every read's counts from one device program, and
    false once one count of every answer is planted one too large.
"""

from __future__ import annotations

import hashlib
import json
import os

import numpy as np
import pytest

from cellbench import refs, run_cell
from cellbench.lib import bytes_fns, check
from cellbench.lib.data import Reference
from cellbench.lib.loadgen import Record
from cellbench.lib.traffic import Generator, Op
from cellbench.templates import topn_src

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHILD = [os.path.join(ROOT, "tests", "rw95_child.py")]


def _json(*parts: str) -> dict:
    with open(os.path.join(ROOT, "cellbench", *parts)) as f:
        return json.load(f)


def _config(name: str, **cut) -> dict:
    return dict(_json("configs", name + ".json"), **cut)


# -- (a) nothing moved --------------------------------------------------------

MIXES = {"count-hot24": "baseline-c4", "count-hot24-solo": "baseline-c4",
         "count-zipf099": "baseline-c4-share8",
         "rw95-zipf099": "baseline-c4-share8-ycsb-b"}

# sha256 of the first 3,000 PQL texts, joined by newlines, at 2 slices
PQL = {
    ("count-hot24", 1): "64a7c8b64a1e551bc52c37cab966659f"
                        "29d1a49e9cdd5ef62eafeb64447ae7fa",
    ("count-hot24", 2): "7b16be50158e5b302bee7a8defb1c685"
                        "861b3bc690458b7209d3ff396a080228",
    ("count-hot24", 3): "c7175977f513e6695141e00693846b78"
                        "7e8f83ae1f360aa228042a448875b96d",
    ("count-zipf099", 1): "25697b2306ecb243cff83b13cd4bda28"
                          "feec6661f2408711605a87cab5964d31",
    ("count-zipf099", 2): "fcebcd3616cc86bc11a0651ffdfc4fc3"
                          "2e024ea8eba6f5d9380c29327b6c0ca9",
    ("count-zipf099", 3): "39ff3d9025cf03a0191e734f8bd5e693"
                          "fabe3da893e0e2c82b85b914c45fb3a1",
    ("rw95-zipf099", 1): "06991dcad6af9bb92430fcbba6e94e63"
                         "6e4ee50bbdeaa03138cdd08308fa89aa",
    ("rw95-zipf099", 2): "f5820ea8570c6711c7a6c438c3d24c93"
                         "50bac14f17b2b8c2d4943702ad2a37bf",
    ("rw95-zipf099", 3): "da4ffffaa10def820e1c10c02264b932"
                         "c7233f7971be33786106212bd2890610",
}
for _seed in (1, 2, 3):     # the solo mix sends the hot mix's requests
    PQL[("count-hot24-solo", _seed)] = PQL[("count-hot24", _seed)]

# sha256 of the reference's rows (packed u64 words) at 2 slices
ROWS = {
    ("baseline-c4", 1): "b61b8f4495bcd8ee6df578e2a9cd303b"
                        "caca7eb776b9105565cfb8d64aaef96c",
    ("baseline-c4", 2): "427ac867a64062f8c83cf07e5d4e9e37"
                        "53e6158c2334de6a1b5a2a64e636e179",
    ("baseline-c4", 3): "005d062419c40c725dca0db189c5c7d2"
                        "2464164643c0d786c7044fbf37b32fd7",
    ("baseline-c4-share8", 1): "b6378b4bd40f19c693789df573745f26"
                               "14fb4a8f1ee093f7a4b5659cf462a42a",
    ("baseline-c4-share8", 2): "b0deac89ca077b215902d19a48e0a239"
                               "72659cc8b9a7f18506e4b0796b9f76b3",
    ("baseline-c4-share8", 3): "0e2024bdda06b99409bfde82ecd5b8b0"
                               "6fabe7769f5cf17b43fad757c1057f3c",
}
for _seed in (1, 2, 3):     # YCSB-B's generator keys are share8's
    ROWS[("baseline-c4-share8-ycsb-b", _seed)] = \
        ROWS[("baseline-c4-share8", _seed)]


@pytest.mark.parametrize("mix, seed", sorted(PQL))
def test_a_seed_sends_the_same_requests_in_the_same_order(mix, seed):
    gen = Generator(_json("traffic", mix + ".json"),
                    _config(MIXES[mix], n_slices=2), seed)
    texts = "\n".join(gen.next().pql for _ in range(3000))
    assert hashlib.sha256(texts.encode()).hexdigest() == PQL[(mix, seed)]


@pytest.mark.parametrize("config, seed", sorted(ROWS))
def test_a_seed_makes_the_same_reference(config, seed):
    ref = refs.make(seed, _config(config, n_slices=2))
    assert type(ref) is Reference       # no configuration names another
    assert hashlib.sha256(ref.rows.tobytes()).hexdigest() \
        == ROWS[(config, seed)]


def test_the_checked_reads_are_the_same_sample():
    assert check.sample_reads(list(range(1000)), 8, 2**31 + 11) \
        == [6, 97, 212, 301, 404, 835, 930, 999]
    assert check.sample_reads(list(range(1000)), 8, 11) \
        == [201, 231, 257, 320, 619, 868, 918, 919]


RW = _json("traffic", "rw95-zipf099.json")
CLS = {o["name"]: o for o in RW["ops"]}


def _col(ref: Reference, want: dict) -> int:
    """The first column whose bits in the given rows are as asked."""
    ok = np.ones(ref.n_cols, dtype=bool)
    for r, v in want.items():
        bits = np.unpackbits(ref.rows[r].view(np.uint8), bitorder="little")
        ok &= bits.astype(bool) == v
    return int(np.flatnonzero(ok)[0])


def _read(ref: Reference, rows, sent, done, plus=0) -> Record:
    leaves = ", ".join(f'Bitmap(frame="f", rowID={r})' for r in rows)
    rec = Record(Op(CLS[f"count_intersect{len(rows)}"], tuple(rows), None,
                    f"Count(Intersect({leaves}))"))
    rec.sent, rec.done, rec.status = sent, done, 200
    rec.results = [ref.count_intersect(rows) + plus]
    return rec


def _write(row, col, sent, done, changed, ok=True) -> Record:
    rec = Record(Op(CLS["setbit"], (row,), col,
                    f'SetBit(frame="f", rowID={row}, columnID={col})'))
    rec.sent, rec.done = sent, done
    rec.status = 200 if ok else 500
    rec.results = [changed] if ok else None
    return rec


def _fixed_records(ref: Reference):
    """(window records, writes before the window, read-back by row)."""
    c0 = _col(ref, {2: 0, 3: 1})        # joins rows 2 & 3 once written
    c1 = _col(ref, {2: 1, 3: 0})
    c2 = _col(ref, {4: 0, 5: 0})
    c3 = _col(ref, {0: 1})
    earlier = [_write(2, c0, 0.0, 0.1, True)]           # sent before
    window = [
        _read(ref, (2, 3), 1.0, 1.1, plus=1),            # the early one due
        _write(3, c1, 1.05, 1.2, True),                   # racing ...
        _read(ref, (3, 2), 1.1, 1.15, plus=2),            # ... and shown
        _read(ref, (2, 3), 1.12, 1.18, plus=1),           # ... and not
        _read(ref, (2, 3), 1.15, 1.25, plus=1),           # acked in it
        _write(5, c2, 1.3, 1.4, None, ok=False),          # no ack
        _read(ref, (4, 5, 6), 2.0, 2.1, plus=1),          # planted wrong
        _read(ref, (0, 1), 2.2, 2.3),
        _write(0, c3, 2.4, 2.5, False),                   # bit already set
        _read(ref, (0, 1, 2, 3), 2.6, 2.7),
    ]
    pop = [int(np.bitwise_count(ref.rows[r]).sum()) for r in range(6)]
    readback = {0: pop[0], 2: pop[2] + 1, 3: pop[3] + 1, 5: pop[5]}
    return window, earlier, readback


PLANTED = ('Count(Intersect(Bitmap(frame="f", rowID=4), Bitmap(frame="f",'
           ' rowID=5), Bitmap(frame="f", rowID=6))): got [9], want one of'
           ' [8]')
VERDICTS = {
    # case: (check_sample, row 2's read-back short by, checked,
    #        wrong_reads, wrong_writes, lost_writes, failed_requests,
    #        mismatches)
    "all": (100, 0, (7, 3, 4), (1, 0, 0, 1), [PLANTED]),
    "sampled": (3, 0, (3, 3, 4), (0, 0, 0, 1), []),
    "lost": (100, 1, (7, 3, 4), (1, 0, 1, 1),
             [PLANTED, "row 2 read back 60353, want 60354"]),
}


@pytest.mark.parametrize("case", sorted(VERDICTS))
def test_the_comparison_gives_the_same_verdict(case):
    """One fixed set of records: a write sent before the window, a write
    racing three reads (acked while the last ran), a write with no ack,
    a SetBit on a set bit, and one planted wrong count."""
    sample, short, checked, values, mismatches = VERDICTS[case]
    ref = Reference(7, _config("baseline-c4-share8-ycsb-b", n_slices=1,
                               n_rows=16))
    window, earlier, back = _fixed_records(ref)
    back[2] -= short
    out = check.compare(ref, window, earlier, {"setbit": back}, sample, 11)
    assert out["checked"] == dict(zip(("reads", "writes", "rows_read_back"),
                                      checked))
    assert out["compared"] == {
        k: {"value": v, "limit": 0} for k, v in zip(
            ("wrong_reads", "wrong_writes", "lost_writes",
             "failed_requests"), values)}
    assert out["mismatches"] == mismatches


# -- (b) the TopN cell through the harness -----------------------------------

CELL = "c3-topn1000-src"
TOPN_METRICS = ("topn_device_pct", "topn_src_roofline", "topn_replay_ms",
                "topn_candidates")


def test_the_topn_cell_is_new_entries_and_files():
    bench, cell, config, traffic = run_cell.resolve(CELL)
    entry = next(c for c in bench["configs"] if c["name"] == "baseline-c3")
    assert entry["source"] == config["source"]
    assert len(entry["source"]) <= 200 and 0 < len(cell["why"]) <= 200
    assert entry["reduced"] == [] and config["reduced"] == {}
    assert cell["chips"] == 1 and cell["traffic"] == "topn1000-src"
    assert (config["n_slices"], config["n_rows"], config["n_cols"],
            config["cache_size"]) == (10, 100_000, 10_000_000, 50_000)
    assert config["reference"] == "topn_sparse"
    for m in bench["per_layer"]:
        if m["name"] in TOPN_METRICS:
            assert m["workloads"] == [CELL]
            spec = _json("metrics", m["name"] + ".json")
            for key in ("unit", "better", "source", "layer", "moves"):
                assert spec[key] == m[key], (m["name"], key)
    assert {m["name"] for m in bench["per_layer"]} >= set(TOPN_METRICS)


def test_the_topn_requests_are_zipf_over_the_densest_rows():
    _, _, config, traffic = run_cell.resolve(CELL)
    gen = Generator(traffic, dict(config, n_slices=2), 2**31 + 3)
    ops = [gen.next() for _ in range(4000)]
    rows = np.array([op.rows[0] for op in ops])
    assert rows.max() < 256 and rows.min() == 0
    assert all(op.pql == f'TopN(Bitmap(frame="f", rowID={op.rows[0]}),'
               ' frame="f", n=1000)' for op in ops)
    # Zipf(0.99) over 256 ranks: rank 0 ~ 16 %, rank 1 ~ 8 %
    share = np.bincount(rows, minlength=256) / len(rows)
    assert 0.13 < share[0] < 0.19 and 0.06 < share[1] < 0.10


def test_the_least_bytes_of_a_topn_read():
    _, _, config, traffic = run_cell.resolve(CELL)
    cls = traffic["ops"][0]
    op = Op(cls, (0,), None, "q", args={"n": 1000})
    assert bytes_fns.resolve(cls) is topn_src.topn_src_least
    least = topn_src.topn_src_least(op, config)
    # the source slab (10 x 128 KiB) and the first 9 rows dense in
    # every slice, the next 991 at 4 B a bit: ~69 MB
    assert 68e6 < least < 70e6
    small = dict(config, n_slices=1, n_cols=1 << 20, n_rows=1)
    assert topn_src.topn_src_least(op, small) == 2 * 131072


def _topn_rehearsal(seed: int, trace: bool) -> dict:
    bench, cell, config, traffic = run_cell.resolve(CELL)
    config = dict(config, n_rows=2000, n_cols=2 * (1 << 20) - 777,
                  d0=0.02)
    traffic = dict(traffic, arrival={"loop": "closed", "clients": 2},
                   warm_requests=10, check_sample=100_000)
    return run_cell.run_resolved(bench, cell, config, traffic, seed, 3.0,
                                 trace, n_slices=2, allow_cpu=True,
                                 child=CHILD)


def test_the_topn_rehearsal_reads_correct_from_the_device():
    result = _topn_rehearsal(2**31 + 42, trace=True)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 5
    assert result["checked"]["reads"] == result["attempted"]
    metrics = result["metrics"]
    assert metrics["topn_device_pct"]["value"] == 100.0
    assert metrics["device_served_pct"]["value"] == 100.0
    assert metrics["topn_candidates"]["value"] >= 1
    assert metrics["topn_replay_ms"]["value"] > 0
    assert "topn_src_roofline" not in metrics     # no peak on the CPU


def test_a_planted_plus_one_reads_wrong(monkeypatch):
    answer = topn_src.answer

    def planted(ref, op):
        right = answer(ref, op)

        def with_writes(writes):
            out = [dict(p) for p in right(writes)]
            if out:
                out[0]["count"] += 1
            return out
        return with_writes

    monkeypatch.setattr(topn_src, "answer", planted)
    result = _topn_rehearsal(2**31 + 43, trace=False)
    assert result["correct"] is False
    assert result["compared"]["wrong_reads"]["value"] \
        == result["checked"]["reads"] > 0


def test_a_program_without_the_device_leg_is_refused(monkeypatch):
    from cellbench.lib.server import BenchFailure
    from pilosa_tpu.obs import accounting
    topn_src.require_device_leg()
    monkeypatch.delattr(accounting, "note_topn_src")
    with pytest.raises(BenchFailure, match="no with-source TopN device"):
        topn_src.require_device_leg()


def _surfaces(topn=None, stages=None, requests=0) -> dict:
    vars_ = {} if topn is None else {"topn": topn}
    if stages is not None:
        vars_["queryStages"] = {"read": {
            "requests": requests, "cpuUs": 0, "offThreadCpuUs": 0,
            "stages": stages, "offThread": {}}}
    return {"status": {}, "vars": vars_}


def _read_record(stats: dict, done: float = 1.5) -> Record:
    rec = Record(Op({"name": "topn_src", "template": "topn_src",
                     "bytes_fn": "topn_src_least"}, (0,), None, "q",
                    args={"n": 1000}))
    rec.status, rec.results, rec.done, rec.stats = 200, [[]], done, stats
    return rec


def test_the_topn_readers_are_silent_on_a_program_without_the_leg():
    """The parent has no ``/debug/vars.topn``, no stage ``topn_replay``
    and no ``topnSrc``: every TopN reader reads nothing there."""
    from cellbench.readers import (topn_candidates, topn_device,
                                   topn_replay, topn_src_roofline)
    run = run_cell.Run()
    run.config = run_cell.resolve(CELL)[2]
    run.records = [_read_record({"devicePrograms": 1})]
    run.before = _surfaces(stages={"execute": {"n": 0, "wallUs": 0}})
    run.after = _surfaces(stages={"execute": {"n": 1, "wallUs": 900}},
                          requests=1)
    run.trace = {"t0": 1.0, "t1": 2.0, "busy_s": 0.5}
    run.peak = {"hbm_bytes_per_s": 819e9}
    for reader in (topn_candidates, topn_device, topn_replay,
                   topn_src_roofline):
        assert reader.read(run) is None, reader.__name__


def test_the_topn_readers_on_hand_made_surfaces():
    from cellbench.readers import (topn_candidates, topn_device,
                                   topn_replay, topn_src_roofline)
    run = run_cell.Run()
    run.config = run_cell.resolve(CELL)[2]
    run.records = [_read_record({"topnSrc": "device"}),
                   _read_record({"topnSrc": "device"}, done=0.5),
                   _read_record({"topnSrc": "host"})]
    base = {"srcDevice": 10, "srcHost": 1, "candidates": 500,
            "residentFrames": 1}
    run.before = _surfaces(dict(base),
                           {"topn_replay": {"n": 4, "wallUs": 400}}, 7)
    run.after = _surfaces(dict(base, srcDevice=12, srcHost=2,
                               candidates=3500),
                          {"topn_replay": {"n": 6, "wallUs": 9400}}, 10)
    run.trace = {"t0": 1.0, "t1": 2.0, "busy_s": 0.5}
    run.peak = {"hbm_bytes_per_s": 819e9}
    assert topn_device.read(run) == pytest.approx(100.0 * 2 / 3)
    assert topn_candidates.read(run) == pytest.approx(1000.0)
    assert topn_replay.read(run) == pytest.approx(3.0)     # 9 ms / 3 reads
    # one device-answered read inside the slice: its least bytes
    least = topn_src.topn_src_least(run.records[0].op, run.config)
    assert topn_src_roofline.read(run) == pytest.approx(
        100.0 * least / 819e9 / 0.5)
