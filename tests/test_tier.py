"""Tiered-storage tests (ISSUE 16): the working-set manager, block-
granular cold faulting, the blob tier, and the satellites that ride
the PR.

Tier-1 (fast) legs: demote → block-fault → promote round trips proven
bit-for-bit against the all-resident answer (randomized differential),
the ENOSPC-during-demotion and cold-fetch-failure failpoint legs
(degrade per the ``?partial=1``/503 contract — never a wrong answer),
the crash-window reopen rules (stub + data file coexistence, leftover
fetch staging, failpoint-aborted push), eviction honoring per-tenant
cache shares (+ pinned entries), the ``tier.fault`` corrupt leg
(quarantine, not a wrong answer), the whole-leg Sum/Min/Max pushdown
folds, per-tenant dispatch fairness, and the /debug/tier surface. The
real SIGKILL mid-transition soak is additionally ``slow``.
"""

import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from pilosa_tpu.fault import failpoints
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.storage import bsi
from pilosa_tpu.storage.integrity import CorruptionError
from pilosa_tpu.tier import blob as blob_mod
from pilosa_tpu.tier.ledger import ResidencyLedger
from pilosa_tpu.tier.manager import ColdFetchError, TierManager

pytestmark = pytest.mark.tier


def _holder_with_fragment(path, n_rows=4, seed=7, per_row=3000):
    """A holder with one snapshotted fragment carrying deterministic
    random rows; returns (holder, fragment, {row: sorted bits})."""
    h = Holder(str(path))
    h.open()
    idx = h.create_index("i")
    fr = idx.create_frame("f")
    view = fr.create_view_if_not_exists("standard")
    frag = view.create_fragment_if_not_exists(0)
    rng = np.random.default_rng(seed)
    expect = {}
    for r in range(n_rows):
        cols = np.unique(rng.integers(0, 1 << 20, size=per_row))
        for c in cols.tolist():
            frag.set_bit(r, c)
        expect[r] = sorted(cols.tolist())
    frag.snapshot()
    return h, frag, expect


def _manager(h, tmp, **kw):
    kw.setdefault("resident_budget", 1 << 30)
    kw.setdefault("cold_dir", os.path.join(str(tmp), "_tier"))
    kw.setdefault("blob", "dir")
    mgr = TierManager(h, **kw)
    h.tier = mgr
    mgr.sync()
    return mgr


# -- demotion / block faulting / promotion ------------------------------------


class TestDemoteFault:
    def test_demote_then_block_fault_exact(self, tmp_path):
        h, frag, expect = _holder_with_fragment(tmp_path)
        _manager(h, tmp_path)
        try:
            assert frag.demote_cold() > 0
            assert frag.tier_state == "cold"
            pending0 = len(frag._cold_pending)
            assert pending0 > 0
            # One row's read faults only that row's container blocks.
            assert sorted(frag.row(1).bits()) == expect[1]
            assert 0 < len(frag._cold_pending) < pending0
            # Remaining rows read correctly too (fault as touched).
            for r, bits in expect.items():
                assert sorted(frag.row(r).bits()) == bits
        finally:
            h.close()

    def test_top_promotes_fully(self, tmp_path):
        h, frag, expect = _holder_with_fragment(tmp_path)
        _manager(h, tmp_path)
        try:
            hot_top = [(p.id, p.count) for p in frag.top()]
            assert frag.demote_cold() > 0
            cold_top = [(p.id, p.count) for p in frag.top()]
            assert cold_top == hot_top
            assert frag.tier_state == "hot", \
                "TopN ranks through the count cache — full promote"
        finally:
            h.close()

    def test_randomized_differential_cold_vs_resident(self, tmp_path):
        """The zero-wrong-answers claim: across random demote /
        partial-fault / rechill / promote schedules, every read is
        bit-for-bit the all-resident answer."""
        h, frag, expect = _holder_with_fragment(tmp_path, n_rows=6,
                                                seed=11)
        mgr = _manager(h, tmp_path)
        try:
            hot_counts = {r: frag.row_count(r) for r in expect}
            rng = np.random.default_rng(3)
            for step in range(40):
                op = rng.integers(0, 10)
                if op < 2 and frag.tier_state == "hot":
                    frag.demote_cold()
                elif op < 3 and frag.tier_state == "cold":
                    frag.tier_rechill()
                elif op < 4 and frag.tier_state != "hot":
                    frag.promote(trigger="read")
                r = int(rng.integers(0, len(expect)))
                assert sorted(frag.row(r).bits()) == expect[r], \
                    f"step {step} state {frag.tier_state}"
                assert frag.row_count(r) == hot_counts[r]
            st = mgr.state()
            assert st["enabled"] is True
        finally:
            h.close()

    def test_sync_reconciles_out_of_band_demote(self, tmp_path):
        """An operator-driven demote_cold() bypasses the manager; the
        next sync() must flip the ledger entry to cold (fragment is
        the record) instead of carrying a stale hot footprint, and a
        promote must land the real post-compaction file size."""
        h, frag, _ = _holder_with_fragment(tmp_path)
        mgr = _manager(h, tmp_path)
        try:
            assert mgr.ledger.get(frag).tier == "hot"
            assert frag.demote_cold() > 0
            assert mgr.ledger.get(frag).tier == "hot", \
                "direct demote doesn't notify — sync reconciles"
            mgr.sync()
            e = mgr.ledger.get(frag)
            assert e.tier == "cold"
            assert e.nbytes == os.path.getsize(frag.path)
            frag.promote(trigger="read")
            e = mgr.ledger.get(frag)
            assert e.tier == "hot"
            assert e.nbytes == os.path.getsize(frag.path)
            assert mgr.ledger.resident_bytes() >= e.nbytes
        finally:
            h.close()

    def test_write_on_cold_fragment_promotes_and_lands(self, tmp_path):
        h, frag, expect = _holder_with_fragment(tmp_path)
        _manager(h, tmp_path)
        try:
            assert frag.demote_cold() > 0
            assert frag.set_bit(1, 999_999)
            assert frag.tier_state == "hot"
            assert sorted(frag.row(1).bits()) == sorted(
                expect[1] + [999_999])
        finally:
            h.close()


# -- ENOSPC during demotion ---------------------------------------------------


class TestEnospcDemotion:
    def test_enospc_mid_demotion_keeps_serving(self, tmp_path):
        """A full disk during the demotion snapshot must leave the
        fragment hot, serving, and intact — degradation, never a
        wrong answer."""
        h, frag, expect = _holder_with_fragment(tmp_path)
        mgr = _manager(h, tmp_path)
        try:
            frag.set_bit(0, 777_777)  # op_n > 0 → demotion snapshots
            expect[0] = sorted(expect[0] + [777_777])
            with failpoints.injected("snapshot.write", "enospc"):
                with pytest.raises(OSError):
                    frag.demote_cold()
                assert not mgr._demote(frag, "idle"), \
                    "manager demotion absorbs the OSError"
            assert frag.tier_state == "hot"
            assert mgr.errors >= 1
            for r, bits in expect.items():
                assert sorted(frag.row(r).bits()) == bits
            # Disarmed: demotion lands and the data is still exact.
            assert frag.demote_cold() > 0
            for r, bits in expect.items():
                assert sorted(frag.row(r).bits()) == bits
        finally:
            failpoints.disarm_all()
            h.close()


# -- blob tier: push / fetch / crash windows ----------------------------------


class TestBlobTier:
    def _pushed(self, tmp_path, **holder_kw):
        h, frag, expect = _holder_with_fragment(tmp_path, **holder_kw)
        mgr = _manager(h, tmp_path)
        assert frag.demote_cold() > 0
        assert mgr.push_blob(frag)
        assert frag.tier_state == "blob" and frag.storage is None
        assert os.path.exists(frag.path + ".blob")
        assert not os.path.exists(frag.path)
        return h, frag, expect, mgr

    def test_push_fetch_round_trip_exact(self, tmp_path):
        h, frag, expect, mgr = self._pushed(tmp_path)
        try:
            for r, bits in expect.items():
                assert sorted(frag.row(r).bits()) == bits
            assert frag.tier_state in ("cold", "hot")
            assert not os.path.exists(frag.path + ".blob")
            assert mgr.blob_fetches == 1
        finally:
            h.close()

    def test_stub_survives_reopen(self, tmp_path):
        h, frag, expect, mgr = self._pushed(tmp_path)
        h.close()
        h2 = Holder(str(tmp_path))
        h2.open()
        try:
            frag2 = h2.fragment("i", "f", "standard", 0)
            assert frag2 is not None and frag2.tier_state == "blob"
            _manager(h2, tmp_path)
            for r, bits in expect.items():
                assert sorted(frag2.row(r).bits()) == bits
        finally:
            h2.close()

    def test_crash_window_stub_and_data_file_coexist(self, tmp_path):
        """SIGKILL between stub write and data-file removal leaves
        BOTH on disk: the data file wins on reopen (it was verified
        before the stub landed) and the stub is deleted."""
        h, frag, expect = _holder_with_fragment(tmp_path)
        mgr = _manager(h, tmp_path)
        assert frag.demote_cold() > 0
        keep = frag.path + ".keep"
        shutil.copy(frag.path, keep)
        assert mgr.push_blob(frag)
        os.rename(keep, frag.path)  # restore: the crash window state
        h.close()
        h2 = Holder(str(tmp_path))
        h2.open()
        try:
            frag2 = h2.fragment("i", "f", "standard", 0)
            assert frag2.tier_state == "hot"
            assert not os.path.exists(frag2.path + ".blob"), \
                "data file wins; stale stub removed"
            for r, bits in expect.items():
                assert sorted(frag2.row(r).bits()) == bits
        finally:
            h2.close()

    def test_crash_window_fetch_staging_leftover(self, tmp_path):
        """SIGKILL mid-fetch leaves a ``.fetching`` staging file; the
        retry's os.replace overwrites it and the fetch still lands."""
        h, frag, expect, mgr = self._pushed(tmp_path)
        h.close()
        open(os.path.join(
            os.path.dirname(frag.path),
            os.path.basename(frag.path) + ".fetching"),
            "wb").write(b"torn garbage")
        h2 = Holder(str(tmp_path))
        h2.open()
        try:
            frag2 = h2.fragment("i", "f", "standard", 0)
            assert frag2.tier_state == "blob"
            _manager(h2, tmp_path)
            for r, bits in expect.items():
                assert sorted(frag2.row(r).bits()) == bits
        finally:
            h2.close()

    def test_failed_push_leaves_fragment_cold_and_serving(self,
                                                          tmp_path):
        h, frag, expect = _holder_with_fragment(tmp_path)
        mgr = _manager(h, tmp_path)
        try:
            assert frag.demote_cold() > 0
            with failpoints.injected("tier.fetch", "partition(push)"):
                assert not mgr.push_blob(frag)
            assert frag.tier_state == "cold"
            assert os.path.exists(frag.path)
            for r, bits in expect.items():
                assert sorted(frag.row(r).bits()) == bits
        finally:
            failpoints.disarm_all()
            h.close()

    def test_torn_promotion_degrades_then_heals(self, tmp_path):
        """A fetch torn mid-promotion: the staged .fetching file never
        becomes the data file, the promotion fails blocked (not wrong),
        and the disarmed retry lands the promotion bit-for-bit."""
        h, frag, expect, mgr = self._pushed(tmp_path)
        try:
            with failpoints.injected("tier.fetch", "torn(64)"):
                with pytest.raises(ColdFetchError):
                    frag.promote(trigger="read")
            assert frag.tier_state == "blob"
            assert not os.path.exists(frag.path), \
                "a torn fetch must never become the data file"
            assert mgr.slice_blocked(frag.index, frag.slice)
            failpoints.disarm_all()
            mgr.pass_once()
            assert not mgr.slice_blocked(frag.index, frag.slice)
            frag.promote(trigger="read")
            assert frag.tier_state == "hot"
            for r, bits in expect.items():
                assert sorted(frag.row(r).bits()) == bits
        finally:
            failpoints.disarm_all()
            h.close()

    def test_corrupt_blob_fetch_blocks_never_lies(self, tmp_path):
        """A blob store whose object rotted: the fetch's crc check
        refuses the bytes, the slice is BLOCKED (not served wrong),
        and an intact store unblocks on retry."""
        h, frag, expect, mgr = self._pushed(tmp_path)
        try:
            root = os.path.join(str(tmp_path), "_tier", "blob")
            flipped = []
            for dirpath, _d, files in os.walk(root):
                for name in files:
                    if name.startswith("blk-0-"):
                        p = os.path.join(dirpath, name)
                        raw = bytearray(open(p, "rb").read())
                        raw[0] ^= 0xFF
                        open(p, "wb").write(bytes(raw))
                        flipped.append((p, bytes(raw)))
            assert flipped
            with pytest.raises(ColdFetchError):
                frag.row(0)
            assert mgr.slice_blocked("i", 0)
            assert frag.tier_state == "blob", "no torn local file"
            # Heal the store; the manager's retry pass unblocks.
            for p, raw in flipped:
                fixed = bytearray(raw)
                fixed[0] ^= 0xFF
                open(p, "wb").write(bytes(fixed))
            mgr.pass_once()
            assert not mgr.slice_blocked("i", 0)
            for r, bits in expect.items():
                assert sorted(frag.row(r).bits()) == bits
        finally:
            h.close()


# -- tier.fault corrupt leg ---------------------------------------------------


class TestColdFaultCorruption:
    def test_corrupt_block_quarantines_not_wrong(self, tmp_path):
        h, frag, expect = _holder_with_fragment(tmp_path)
        _manager(h, tmp_path)
        try:
            assert frag.demote_cold() > 0
            with failpoints.injected("tier.fault", "corrupt*1"):
                with pytest.raises(CorruptionError):
                    frag.row(0)
            assert frag.quarantined, \
                "a rotten faulted block is detection → quarantine"
        finally:
            failpoints.disarm_all()
            h.close()


# -- eviction honors per-tenant cache shares ----------------------------------


class _FakeFrag:
    def __init__(self, index, slice):
        self.index, self.frame, self.view = index, "f", "standard"
        self.slice = slice


class TestEvictionShares:
    def test_victims_drain_over_share_tenant_first(self):
        led = ResidencyLedger()
        budget = 1000
        # Tenant b is the OLDEST touch (plain LRU would evict it
        # first); but a is over its share (600 > 0.3×1000) while b is
        # under (200 < 0.5×1000) — so a pays, not the LRU choice.
        fb = _FakeFrag("b", 9)
        led.track(fb, "hot", 200)
        led.touch(fb, "b")
        time.sleep(0.002)
        for i in range(3):
            f = _FakeFrag("a", i)
            led.track(f, "hot", 200)
            led.touch(f, "a")
            time.sleep(0.002)
        shares = {"a": 0.3, "b": 0.5}
        out = led.victims(300, budget, shares)
        assert out and all(k[0] == "a" for k in out), \
            f"over-share tenant pays first, not the LRU pick: {out}"
        # Without shares the same request DOES take b first: the
        # share discipline, not touch order, drove the pick above.
        assert led.victims(300, budget, None)[0][0] == "b"

    def test_under_share_tenant_untouched_until_over_drained(self):
        led = ResidencyLedger()
        fa = _FakeFrag("a", 0)
        led.track(fa, "hot", 800)
        led.touch(fa, "a")
        fb = _FakeFrag("b", 1)
        led.track(fb, "hot", 100)
        led.touch(fb, "b")
        out = led.victims(850, 1000, {"a": 0.2, "b": 0.5})
        assert out[0][0] == "a"
        assert out[1][0] == "b", "only after a is drained"

    def test_pinned_entries_never_victims(self):
        led = ResidencyLedger()
        fa = _FakeFrag("a", 0)
        led.track(fa, "hot", 500)
        led.pin(fa, True)
        fb = _FakeFrag("a", 1)
        led.track(fb, "hot", 500)
        out = led.victims(100, 1000, {"a": 0.1})
        assert out == [("a", "f", "standard", 1)]

    def test_manager_evict_respects_shares_end_to_end(self, tmp_path):
        """Watermark pressure on a real holder: the over-share index
        (= tenant) is demoted, the under-share one stays hot."""
        from pilosa_tpu.sched.tenants import TenantRegistry
        h = Holder(str(tmp_path))
        h.open()
        frags = {}
        for name in ("big", "small"):
            idx = h.create_index(name)
            view = idx.create_frame("f").create_view_if_not_exists(
                "standard")
            frag = view.create_fragment_if_not_exists(0)
            n = 30000 if name == "big" else 200
            for c in range(0, n * 30, 30):
                frag.set_bit(0, c)
            frag.snapshot()
            frags[name] = frag
        size_big = os.path.getsize(frags["big"].path)
        size_small = os.path.getsize(frags["small"].path)
        budget = size_big + size_small  # resident ≈ budget
        reg = TenantRegistry({"big": {"cache_share": 0.1},
                              "small": {"cache_share": 1.0}})
        mgr = TierManager(h, resident_budget=budget,
                          high_watermark=0.8, low_watermark=0.5,
                          cold_dir=os.path.join(str(tmp_path), "_t"),
                          tenants=reg, pace_s=0.0)
        h.tier = mgr
        mgr.sync()
        try:
            for name, frag in frags.items():
                mgr.ledger.touch(frag, name)
            mgr.pass_once()
            assert frags["big"].tier_state == "cold", \
                "over-share tenant absorbs its own pressure"
            assert frags["small"].tier_state == "hot", \
                "under-share tenant's working set survives"
        finally:
            h.close()


# -- serving contract: cold-fetch failure through the server ------------------


def _post(host, path, body=b"", timeout=30):
    req = urllib.request.Request(f"http://{host}{path}", data=body,
                                 method="POST")
    return urllib.request.urlopen(req, timeout=timeout)


def _query(host, index, pql, qs=""):
    return _post(host, f"/index/{index}/query{qs}", pql.encode())


@pytest.fixture
def tiered_solo(tmp_path, monkeypatch):
    monkeypatch.setenv("PILOSA_TPU_MESH", "0")
    from pilosa_tpu.server.server import Server
    from pilosa_tpu.utils.config import ScrubConfig, TierConfig
    s = Server(str(tmp_path / "solo"), host="127.0.0.1:0",
               anti_entropy_interval=0, polling_interval=0,
               scrub_config=ScrubConfig(interval=999.0, pace=0.0,
                                        repair=False),
               tier_config=TierConfig(enabled=True,
                                      resident_budget=1 << 30,
                                      idle=999.0, blob_idle=999.0,
                                      interval=999.0, blob="dir",
                                      pace=0.0))
    s.open()
    _post(s.host, "/index/it", b"{}")
    _post(s.host, "/index/it/frame/f", b"{}")
    for col in (3, 9, 77):
        _query(s.host, "it",
               f'SetBit(frame="f", rowID=1, columnID={col})')
    yield s
    failpoints.disarm_all()
    s.close()


class TestColdFetchContract:
    def _to_blob(self, s):
        frag = s.holder.fragment("it", "f", "standard", 0)
        frag.snapshot()
        s.tier.sync()  # hook the fragment (the 999s loop hasn't)
        assert s.tier._demote(frag, "idle")
        assert s.tier.push_blob(frag)
        return frag

    def test_fetch_failure_degrades_then_retry_heals(self,
                                                     tiered_solo):
        s = tiered_solo
        count_q = 'Count(Bitmap(frame="f", rowID=1))'
        assert json.loads(
            _query(s.host, "it", count_q).read())["results"][0] == 3
        self._to_blob(s)
        failpoints.arm("tier.fetch", "partition(fetch)")
        try:
            # Plain query: 5xx, NEVER a wrong count.
            with pytest.raises(urllib.error.HTTPError) as ei:
                _query(s.host, "it", count_q)
            assert ei.value.code in (500, 503)
            # The slice is now blocked: the degraded-read contract.
            assert s.tier.slice_blocked("it", 0)
            resp = _query(s.host, "it", count_q, qs="?partial=1")
            assert resp.status == 200
            assert resp.headers.get("X-Pilosa-Partial") == "0"
            assert json.loads(resp.read())["results"][0] == 0
        finally:
            failpoints.disarm_all()
        # Store reachable again: the manager retry unblocks and the
        # exact answer comes back.
        s.tier.pass_once()
        assert not s.tier.slice_blocked("it", 0)
        assert json.loads(
            _query(s.host, "it", count_q).read())["results"][0] == 3

    def test_debug_tier_surface(self, tiered_solo):
        s = tiered_solo
        out = json.loads(urllib.request.urlopen(
            f"http://{s.host}/debug/tier", timeout=10).read())
        assert out["enabled"] is True
        assert "tiers" in out and "residentBytes" in out
        frag = self._to_blob(s)
        out = json.loads(urllib.request.urlopen(
            f"http://{s.host}/debug/tier?entries=1&pass=1",
            timeout=10).read())
        assert out["tiers"]["blob"]["fragments"] == 1
        assert any(e["tier"] == "blob" for e in out["entries"])
        assert "pass" in out
        # The blackbox carries a tier block.
        bb = s._blackbox_state()
        assert bb["tier"]["enabled"] is True
        assert frag.tier_state == "blob"

    def test_scrub_pass_covers_blob_tier(self, tiered_solo):
        s = tiered_solo
        self._to_blob(s)
        out = s.scrubber.pass_once()
        assert out["fragments"] >= 1 and out["corrupt"] == 0
        # Rot a blob object: the NEXT pass flags it and blocks the
        # slice (no local bytes to quarantine).
        root = os.path.join(s.tier.cold_dir, "blob")
        for dirpath, _d, files in os.walk(root):
            for name in files:
                if name.startswith("blk-"):
                    p = os.path.join(dirpath, name)
                    raw = bytearray(open(p, "rb").read())
                    raw[0] ^= 0xFF
                    open(p, "wb").write(bytes(raw))
        out = s.scrubber.pass_once()
        assert out["corrupt"] == 1


# -- whole-leg Sum/Min/Max pushdown folds -------------------------------------


class TestAggregateLegFolds:
    def _legs(self, rng, n_slices, depth, with_filter):
        """Synthetic per-slice plane rows as roaring bitmaps."""
        from pilosa_tpu.storage import roaring
        legs, values = [], []
        for _s in range(n_slices):
            n = int(rng.integers(1, 50))
            cols = rng.choice(2000, size=n, replace=False)
            vals = rng.integers(0, 1 << depth, size=n)
            rows = {}
            exists = roaring.Bitmap()
            for c, v in zip(cols.tolist(), vals.tolist()):
                exists.add(c)
                for i in range(depth):
                    if (v >> i) & 1:
                        rows.setdefault(i, roaring.Bitmap()).add(c)
            filt = None
            mask = np.ones(n, dtype=bool)
            if with_filter:
                filt = roaring.Bitmap()
                mask = rng.integers(0, 2, size=n).astype(bool)
                for c in cols[mask].tolist():
                    filt.add(c)

            def row(plane, _ex=exists, _rows=rows):
                if plane == bsi.EXISTS_PLANE:
                    return _ex
                return _rows.get(plane, roaring.Bitmap())
            legs.append((row, filt))
            values.extend(vals[mask].tolist())
        return legs, values

    @pytest.mark.parametrize("with_filter", [False, True])
    def test_sum_min_max_many_match_per_slice(self, with_filter):
        rng = np.random.default_rng(5)
        for trial in range(8):
            depth = int(rng.integers(1, 9))
            min_v, max_v = 0, (1 << depth) - 1
            legs, values = self._legs(rng, int(rng.integers(1, 6)),
                                      depth, with_filter)
            got = bsi.sum_count_many(min_v, max_v, legs)
            # Per-slice + combine is the reference semantics.
            ref = None
            for row, filt in legs:
                v = bsi.sum_count(min_v, max_v, row, filter=filt)
                ref = v if ref is None else bsi.combine_sum(ref, v)
            assert (got.value, got.count) == (ref.value, ref.count)
            assert got.value == sum(values)
            for want_min in (True, False):
                got = bsi.min_max_many(min_v, max_v, legs,
                                       want_min=want_min)
                ref = None
                for row, filt in legs:
                    v = bsi.min_max(min_v, max_v, row, filter=filt,
                                    want_min=want_min)
                    ref = (v if ref is None
                           else bsi.combine_min_max(
                               ref, v, want_min=want_min))
                assert (got.value, got.count) == (ref.value,
                                                 ref.count), \
                    f"trial {trial} want_min={want_min}"
                if values:
                    ext = min(values) if want_min else max(values)
                    assert got.value == ext

    def test_executor_aggregate_over_cold_fragments(self, tmp_path):
        """Sum/Min/Max through the executor leg against demoted
        fragments equals the all-resident answer (the pushdown runs
        on faulted-in blocks)."""
        from pilosa_tpu.executor import Executor
        from pilosa_tpu.models.frame import Field
        h = Holder(str(tmp_path))
        h.open()
        idx = h.create_index("i")
        fr = idx.create_frame("f")
        fr.create_field(Field("v", 0, 1000))
        rng = np.random.default_rng(13)
        model = {}
        ex = Executor(h, host="local", use_mesh=False)
        try:
            for col in rng.choice(5000, size=300,
                                  replace=False).tolist():
                val = int(rng.integers(0, 1001))
                ex.execute("i", f'SetFieldValue(frame="f",'
                                f' columnID={col}, v={val})')
                model[col] = val
            hot = {}
            for name in ("Sum", "Min", "Max"):
                hot[name] = ex.execute(
                    "i", f'{name}(frame="f", field="v")')[0].to_json()
            assert hot["Sum"]["value"] == sum(model.values())
            assert hot["Min"]["value"] == min(model.values())
            assert hot["Max"]["value"] == max(model.values())
            _manager(h, tmp_path)
            for frag in list(h.iter_fragments()):
                frag.snapshot()
                assert frag.demote_cold() > 0
            for name in ("Sum", "Min", "Max"):
                cold = ex.execute(
                    "i", f'{name}(frame="f", field="v")')[0].to_json()
                assert cold == hot[name], f"{name} differs cold"
        finally:
            ex.close()
            h.close()

    def test_executor_topn_hot_equals_blob(self, tmp_path):
        """Plain TopN through the executor's batched host path ranks
        via the count caches, which demotion drops — a cold/blob
        fragment must promote before ranking, never answer from the
        empty cache (the wrong-answer path the end-to-end drive
        caught)."""
        from pilosa_tpu.executor import Executor
        h, frag, expect = _holder_with_fragment(tmp_path)
        mgr = _manager(h, tmp_path)
        ex = Executor(h, host="local", use_mesh=False)
        try:
            hot = [(p.id, p.count) for p in
                   ex.execute("i", 'TopN(frame="f", n=3)')[0]]
            assert hot, "seed data must rank"
            assert frag.demote_cold() > 0
            assert mgr.push_blob(frag)
            blob = [(p.id, p.count) for p in
                    ex.execute("i", 'TopN(frame="f", n=3)')[0]]
            assert blob == hot, "TopN through blob tier differs"
            assert frag.tier_state == "hot", "TopN fully promotes"
        finally:
            ex.close()
            h.close()


# -- per-tenant device-queue fairness -----------------------------------------


class TestFairDispatch:
    def test_uncontended_fast_path_no_wait(self):
        from pilosa_tpu.parallel.mesh import FairDispatchQueue
        q = FairDispatchQueue(4)
        q.acquire("a")
        q.release()
        st = q.state()
        assert st["waits"] == 0 and st["inFlight"] == 0
        assert st["dispatches"] == 1

    def test_stride_wake_order_is_weighted(self):
        """Deterministic stride order: with slots saturated, waiters
        wake lowest-pass-first — weight 2 tenant b interleaves ahead
        of weight 1 tenant a's backlog."""
        from pilosa_tpu.parallel.mesh import FairDispatchQueue
        weights = {"a": 1.0, "b": 2.0}
        q = FairDispatchQueue(1, weights.get)
        q.acquire("hold")  # saturate the single slot
        order = []
        started = []

        def waiter(tenant):
            started.append(tenant)
            q.acquire(tenant)
            order.append(tenant)
            q.release()

        threads = []
        # Enqueue order: a, a, a, then b, b — strides put b's first
        # two passes (0.5, 1.0) ahead of a's backlog (1.0, 2.0, 3.0).
        for tenant in ("a", "a", "a", "b", "b"):
            t = threading.Thread(target=waiter, args=(tenant,))
            t.start()
            while len(started) < len(threads) + 1:
                time.sleep(0.001)
            deadline = time.monotonic() + 5
            while q.state()["queued"] < len(threads) + 1:
                assert time.monotonic() < deadline
                time.sleep(0.001)
            threads.append(t)
        q.release()  # free the held slot: the queue drains in order
        for t in threads:
            t.join(timeout=5)
        assert order == ["b", "a", "b", "a", "a"]

    def test_a_free_slot_is_never_waited_for_by_eight_threads_at_once(self):
        """8 slots for 8 callers (every cell): ``waits`` stays 0, every
        dispatch is counted, nothing is left in flight."""
        from pilosa_tpu.parallel.mesh import FairDispatchQueue
        q = FairDispatchQueue(8)

        def caller():
            for _ in range(500):
                q.acquire("i")
                q.release()

        threads = [threading.Thread(target=caller) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        assert q.state() == {"slots": 8, "inFlight": 0, "queued": 0,
                             "dispatches": 4000, "waits": 0}

    def test_a_cancelled_waiter_hands_its_slot_on(self):
        """A query killed while queued leaves the queue without a slot,
        and the slot it would have had goes to the waiter behind it."""
        from pilosa_tpu.errors import QueryCancelledError
        from pilosa_tpu.parallel.mesh import FairDispatchQueue
        from pilosa_tpu.sched import QueryContext
        from pilosa_tpu.sched import context as sched_context
        q = FairDispatchQueue(1)
        q.acquire("hold")
        ctx = QueryContext(pql="Count()")
        raised, order = [], []

        def doomed():
            with sched_context.use(ctx):
                try:
                    q.acquire("a")
                    order.append("a")
                except QueryCancelledError as e:
                    raised.append(e)

        def patient():
            q.acquire("b")
            order.append("b")

        ta = threading.Thread(target=doomed)
        ta.start()
        deadline = time.monotonic() + 5
        while q.state()["queued"] < 1:
            assert time.monotonic() < deadline
            time.sleep(0.001)
        tb = threading.Thread(target=patient)
        tb.start()
        while q.state()["queued"] < 2:
            assert time.monotonic() < deadline
            time.sleep(0.001)
        ctx.cancel("test")
        ta.join(timeout=5)
        assert raised and not ta.is_alive()
        assert q.state()["queued"] == 1 and order == []
        q.release()                     # the held slot goes to b, not to a
        tb.join(timeout=5)
        assert order == ["b"] and q.state()["inFlight"] == 1
        q.release()
        assert q.state() == {"slots": 1, "inFlight": 0, "queued": 0,
                             "dispatches": 3, "waits": 2}

    def test_server_installs_and_uninstalls(self, tiered_solo):
        from pilosa_tpu.parallel import mesh as mesh_mod
        st = mesh_mod.fair_dispatch_state()
        assert st is not None and st["slots"] >= 1


# -- SIGKILL mid-transition (slow) --------------------------------------------


_KILL_CHILD = r"""
import os, sys, time
import numpy as np
sys.path.insert(0, {repo!r})
os.environ["JAX_PLATFORMS"] = "cpu"
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.tier.manager import TierManager

data = sys.argv[1]
h = Holder(data)
h.open()
idx = h.create_index("i")
view = idx.create_frame("f").create_view_if_not_exists("standard")
frag = view.create_fragment_if_not_exists(0)
rng = np.random.default_rng(17)
for r in range(4):
    for c in np.unique(rng.integers(0, 1 << 20, size=2000)).tolist():
        frag.set_bit(r, c)
frag.snapshot()
mgr = TierManager(h, resident_budget=1 << 30,
                  cold_dir=os.path.join(data, "_tier"), blob="dir",
                  pace_s=0.0)
h.tier = mgr
mgr.sync()
print("READY", flush=True)
while True:  # demote/fault/promote/push/fetch until SIGKILLed
    frag.demote_cold()
    frag.row(1)
    frag.promote(trigger="read")
    frag.demote_cold()
    mgr.push_blob(frag)
    frag.row(2)          # fetch + fault
    frag.promote(trigger="read")
"""


@pytest.mark.slow
@pytest.mark.chaos
def test_sigkill_mid_transition_reopens_clean(tmp_path):
    """SIGKILL a process hammering demote/promote/push/fetch cycles,
    at random points, repeatedly: every reopen must see EXACTLY the
    snapshotted bits — no tier transition window loses or invents
    data."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    child_src = _KILL_CHILD.format(repo=repo)
    script = tmp_path / "child.py"
    script.write_text(child_src)
    data = str(tmp_path / "data")
    rng = np.random.default_rng(17)
    expect = {r: sorted(np.unique(
        rng.integers(0, 1 << 20, size=2000)).tolist())
        for r in range(4)}
    for trial in range(4):
        proc = subprocess.Popen(
            [sys.executable, str(script), data],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True)
        assert proc.stdout.readline().strip() == "READY"
        time.sleep(0.05 + 0.2 * trial)
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
        h = Holder(data)
        h.open()
        try:
            frag = h.fragment("i", "f", "standard", 0)
            assert frag is not None, f"trial {trial}: fragment gone"
            mgr = TierManager(h, resident_budget=1 << 30,
                              cold_dir=os.path.join(data, "_tier"),
                              blob="dir", pace_s=0.0)
            h.tier = mgr
            mgr.sync()
            for r, bits in expect.items():
                assert sorted(frag.row(r).bits()) == bits, \
                    f"trial {trial} row {r} diverged after SIGKILL"
        finally:
            h.close()
        shutil.rmtree(data, ignore_errors=True)


# -- blob store unit ----------------------------------------------------------


class TestBlobStore:
    def test_open_specs(self, tmp_path):
        assert blob_mod.open_blob_store("", str(tmp_path)) is None
        s = blob_mod.open_blob_store("dir", str(tmp_path))
        assert isinstance(s, blob_mod.LocalDirBlobStore)
        s2 = blob_mod.open_blob_store(
            f"dir:{tmp_path}/custom", str(tmp_path))
        assert "custom" in s2.root
        with pytest.raises(ValueError):
            blob_mod.open_blob_store("s3://nope", str(tmp_path))

    def test_check_deep_walks_blob_stubs(self, tmp_path):
        """``pilosa-tpu check --deep`` covers blob-tier fragments:
        clean verdicts, then a corrupt object flips rc to 1."""
        import argparse
        import io

        from pilosa_tpu.cli import commands as cmds
        h, frag, _ = _holder_with_fragment(tmp_path)
        mgr = _manager(h, tmp_path)
        assert frag.demote_cold() > 0
        assert mgr.push_blob(frag)
        h.close()
        out = io.StringIO()
        rc = cmds.cmd_check(
            argparse.Namespace(paths=[str(tmp_path)], deep=True),
            out, out)
        assert rc == 0 and "blob tier" in out.getvalue()
        root = os.path.join(str(tmp_path), "_tier", "blob")
        for dirpath, _d, files in os.walk(root):
            for name in files:
                if name.startswith("blk-0-"):
                    p = os.path.join(dirpath, name)
                    raw = bytearray(open(p, "rb").read())
                    raw[-1] ^= 0x01
                    open(p, "wb").write(bytes(raw))
        out = io.StringIO()
        rc = cmds.cmd_check(
            argparse.Namespace(paths=[str(tmp_path)], deep=True),
            out, out)
        assert rc == 1 and "CORRUPT" in out.getvalue()
