"""TierManager: the working-set manager's control loops.

One paced background loop per node (plus an optional prefetch loop)
runs four phases over the residency ledger each pass:

1. **Sync** — reconcile the ledger with the holder's open fragments
   (new fragments enter at their current tier, closed ones drop out)
   and refresh hot byte footprints.
2. **Idle demotion** — hot fragments untouched for ``[tier] idle``
   close to a checksummed cold snapshot (``Fragment.demote_cold``:
   WAL barrier → op-log fold → metadata-only reopen).
3. **Watermark eviction** — when resident bytes exceed
   ``high_watermark × resident_budget``, the ledger's victim order
   (over-cache-share tenants first, LRU within; see tier.ledger)
   demotes hot fragments and re-chills cold ones until resident falls
   to the low watermark.
4. **Blob push** — cold fragments untouched for ``blob_idle`` leave
   local disk through the pluggable blob store (tier.blob block-diff
   push); a ``<path>.blob`` stub keeps them discoverable.

Cold-fetch failures (``ColdFetchError``) mark the fragment's
(index, slice) **blocked**: the executor consults
``holder.tier_blocked`` exactly like the quarantine registry's
``slice_blocked``, so reads fail over / degrade per the ``?partial=1``
contract instead of returning a wrong answer. The loop retries
blocked fetches each pass and unblocks on success — self-healing, no
operator action.

The prefetcher reads the ``pilosa_tier_fragment_touches_total`` rate
series from the on-disk metric history (obs.history) — the same
per-(tenant, index, slice) touch counter the read gate feeds — and
promotes the hottest cold fragments while resident stays under the
low watermark, skipping entirely when admission is busy.

Lock discipline: the ledger is a leaf lock (never held while taking
fragment locks); transitions take ``frag._snap_mu`` then ``frag._mu``
(the fragment's own close/snapshot order); the manager's ``_mu``
guards only its maps and is never held across a transition.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Optional

from ..fault import failpoints as _fp
from ..obs import metrics as obs_metrics
from ..sched import context as sched_context
from ..storage import integrity as integrity_mod
from ..utils import logger as logger_mod
from . import blob as blob_mod
from .ledger import BLOB, COLD, HOT, ResidencyLedger

DEFAULT_INTERVAL_S = 10.0
DEFAULT_PACE_S = 0.01

TOUCH_FAMILY = "pilosa_tier_fragment_touches_total"


class ColdFetchError(OSError):
    """A blob-tier fragment could not be materialized (store
    unreachable, objects missing, reassembly failed verification).
    Subclasses OSError so transport-style error handling treats it as
    'the read failed here' — the executor fails the slice over /
    degrades per the partial contract, never serves a guess."""


class TierManager:
    def __init__(self, holder, *, resident_budget: int = 0,
                 high_watermark: float = 0.9,
                 low_watermark: float = 0.7,
                 idle_s: float = 300.0, blob_idle_s: float = 3600.0,
                 cold_dir: str = "", blob: str = "",
                 interval_s: float = DEFAULT_INTERVAL_S,
                 prefetch_interval_s: float = 0.0,
                 pace_s: float = DEFAULT_PACE_S,
                 tenants=None, history=None, busy_fn=None,
                 logger=None):
        self.holder = holder
        self.ledger = ResidencyLedger()
        self.resident_budget = int(resident_budget)
        self.high_watermark = float(high_watermark)
        self.low_watermark = min(float(low_watermark),
                                 float(high_watermark))
        self.idle_s = float(idle_s)
        self.blob_idle_s = float(blob_idle_s)
        self.cold_dir = cold_dir
        if cold_dir:
            os.makedirs(cold_dir, exist_ok=True)
        self.store = blob_mod.open_blob_store(blob, cold_dir or ".")
        self.interval_s = max(0.05, float(interval_s))
        self.prefetch_interval_s = float(prefetch_interval_s)
        self.pace_s = max(0.0, float(pace_s))
        self.tenants = tenants          # sched.tenants.TenantRegistry
        self.history = history          # obs.history.MetricHistory
        self.busy_fn = busy_fn          # () -> bool: admission busy?
        self.logger = logger or logger_mod.NOP
        self._mu = threading.Lock()
        self._frags: dict[tuple, object] = {}
        # (index, frame, view, slice) -> {"reason", "since"}; the
        # slice rollup mirrors QuarantineRegistry.slice_blocked.
        self._blocked: dict[tuple, dict] = {}
        self._blocked_slices: dict[tuple, int] = {}
        # Stall bookkeeping (the watchdog's tier_stall input): work
        # was pending at the end of a pass but no transition has
        # completed since _last_transition.
        self._work_pending = False
        self._last_transition = time.monotonic()
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []
        # Lifetime counters for /debug/tier (metrics carry the same
        # numbers; these avoid a registry scrape in state()).
        self.demotions = 0
        self.rechills = 0
        self.promotions = 0
        self.blob_pushes = 0
        self.blob_fetches = 0
        self.fetch_failures = 0
        self.errors = 0

    # -- lifecycle ------------------------------------------------------------

    def start(self) -> None:
        t = threading.Thread(target=self._run, name="pilosa-tier",
                             daemon=True)
        t.start()
        self._threads = [t]
        if self.prefetch_interval_s > 0:
            p = threading.Thread(target=self._run_prefetch,
                                 name="pilosa-tier-prefetch",
                                 daemon=True)
            p.start()
            self._threads.append(p)

    def stop(self) -> None:
        self._stop.set()
        for t in self._threads:
            t.join(timeout=5.0)

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                with sched_context.background_tick("tier"):
                    self.pass_once()
            except Exception as e:  # noqa: BLE001 - the loop must not die
                self.logger.printf("tier: pass failed: %s", e)

    def _run_prefetch(self) -> None:
        while not self._stop.wait(self.prefetch_interval_s):
            try:
                self.prefetch_once()
            except Exception as e:  # noqa: BLE001
                self.logger.printf("tier: prefetch failed: %s", e)

    # -- the pass -------------------------------------------------------------

    def pass_once(self) -> dict:
        """One manager pass; returns a summary for tests/debug."""
        self.sync()
        demoted = self._demote_idle()
        evicted = self._evict()
        pushed = self._push_idle()
        retried = self._retry_blocked()
        self.ledger.update_gauges()
        # Work is "pending" when pressure remains that only a future
        # transition can relieve: still over the high watermark, or
        # blocked fetches outstanding. The watchdog trips tier_stall
        # when this stays true with no transition completing.
        over = (self.resident_budget > 0
                and self.ledger.resident_bytes()
                > int(self.high_watermark * self.resident_budget))
        self._work_pending = over or bool(self._blocked)
        return {"demoted": demoted, "evicted": evicted,
                "pushed": pushed, "retried": retried}

    def sync(self) -> None:
        """Reconcile ledger + fragment hooks with the holder."""
        seen = set()
        for frag in self.holder.iter_fragments():
            if not getattr(frag, "_open", False):
                continue
            key = self.ledger.key_of(frag)
            seen.add(key)
            with self._mu:
                known = self._frags.get(key)
                self._frags[key] = frag
            if known is not frag:
                frag.tier = self
            st = getattr(frag, "tier_state", HOT)
            e = self.ledger.get(frag)
            if e is None:
                self.ledger.track(frag, st, self._frag_bytes(frag, st))
            elif e.tier != st:
                # Out-of-band transition (operator demote_cold, crash
                # recovery): the fragment is the record, not the ledger.
                self.ledger.set_tier(frag, st, self._frag_bytes(frag, st))
            elif e.tier == HOT:
                # Hot footprints drift as writes land; refresh.
                e.nbytes = self._frag_bytes(frag, HOT)
        with self._mu:
            gone = [(k, self._frags.pop(k))
                    for k in list(self._frags) if k not in seen]
        for key, frag in gone:
            self.ledger.forget(frag)
            self._unblock_key(key)

    @staticmethod
    def _frag_bytes(frag, tier: str) -> int:
        path = frag.path if tier != BLOB else frag.path + ".blob"
        try:
            if tier == BLOB:
                with open(path, "rb") as f:
                    return int(json.load(f).get("size", 0))
            return os.path.getsize(path)
        except (OSError, ValueError):
            return 0

    def _demote_idle(self) -> int:
        if self.idle_s <= 0:
            return 0
        n = 0
        for key in self.ledger.idle_hot(self.idle_s):
            if self._stop.is_set():
                break
            frag = self._frags.get(key)
            if frag is not None and self._demote(frag, "idle"):
                n += 1
        return n

    def _evict(self) -> int:
        budget = self.resident_budget
        if budget <= 0:
            return 0
        resident = self.ledger.resident_bytes()
        if resident <= int(self.high_watermark * budget):
            return 0
        need = resident - int(self.low_watermark * budget)
        n = 0
        for key in self.ledger.victims(need, budget, self._shares()):
            if self._stop.is_set():
                break
            frag = self._frags.get(key)
            if frag is None:
                continue
            e = self.ledger.get(frag)
            if e is None:
                continue
            if e.tier == HOT:
                if self._demote(frag, "watermark"):
                    n += 1
            elif e.tier == COLD and e.faulted_bytes > 0:
                if self._rechill(frag):
                    n += 1
        return n

    def _shares(self) -> Optional[dict]:
        reg = self.tenants
        if reg is None:
            return None
        try:
            shares = {name: float(reg.policy(name).cache_share)
                      for name in reg.known()}
        except Exception:  # noqa: BLE001 - shares are advisory
            return None
        # The ledger falls back to shares.get("", 1.0) for tenants
        # with no configured policy; map that to the default policy.
        from ..utils.config import DEFAULT_TENANT
        shares[""] = shares.get(DEFAULT_TENANT, 1.0)
        return shares

    def _demote(self, frag, reason: str) -> bool:
        self.ledger.pin(frag, True)
        try:
            try:
                nbytes = frag.demote_cold()
            except OSError as e:
                # ENOSPC mid-snapshot (or any write failure): the old
                # file stays the record, the fragment stays hot, and
                # the diskfull degradation (507s) throttles writers —
                # demotion just didn't happen this pass.
                self.logger.printf("tier: demotion failed %s/%s/%s/%d:"
                                   " %s", frag.index, frag.frame,
                                   frag.view, frag.slice, e)
                self.errors += 1
                return False
            if nbytes <= 0:
                return False
            self.ledger.set_tier(frag, COLD, nbytes)
            obs_metrics.TIER_DEMOTIONS.labels(reason).inc()
            self.demotions += 1
            self._transition()
        finally:
            self.ledger.pin(frag, False)
        self._pace()
        return True

    def _rechill(self, frag) -> bool:
        """Reclaim a cold fragment's faulted residency by resetting
        its fault set — the cold scanner pays for its own scan."""
        self.ledger.pin(frag, True)
        try:
            if not frag.tier_rechill():
                return False
            self.ledger.set_tier(frag, COLD)  # resets faulted bytes
            obs_metrics.TIER_DEMOTIONS.labels("watermark").inc()
            self.rechills += 1
            self._transition()
        finally:
            self.ledger.pin(frag, False)
        self._pace()
        return True

    # -- blob tier ------------------------------------------------------------

    def _push_idle(self) -> int:
        if self.store is None or self.blob_idle_s <= 0:
            return 0
        n = 0
        for key in self.ledger.idle_cold(self.blob_idle_s):
            if self._stop.is_set():
                break
            frag = self._frags.get(key)
            if frag is not None and self.push_blob(frag):
                n += 1
        return n

    def push_blob(self, frag) -> bool:
        """Move one cold fragment's file into the blob store (block
        diff), then replace it with a ``.blob`` stub. Crash-safe
        order: objects → stub → remove file — at every kill point the
        restart either still has the data file (stub deleted, re-push
        re-diffs) or has a complete stub + pushed objects."""
        if self.store is None:
            return False
        self.ledger.pin(frag, True)
        try:
            with frag._snap_mu, frag._mu:
                if (not frag._open or frag.quarantined
                        or getattr(frag, "tier_state", HOT) != COLD):
                    return False
                storage = frag.storage
                info = getattr(storage, "footer", None)
                if (storage is None or storage.op_n or info is None
                        or info.offsets is None):
                    return False
                end = info.body_len + info.size
                mm = frag._mmap
                if mm is None or len(mm) < end:
                    return False
                buf = bytes(mm[:end])
                prefix = blob_mod.fragment_prefix(
                    frag.index, frag.frame, frag.view, frag.slice)
                try:
                    if _fp.ACTIVE is not None:
                        _fp.ACTIVE.hit("tier.fetch", host="push",
                                       path=frag.path)
                    blob_mod.push_fragment(self.store, prefix, buf,
                                           info)
                except OSError as e:
                    obs_metrics.TIER_FETCHES.labels(
                        "push", "error").inc()
                    self.logger.printf("tier: blob push failed %s: %s",
                                       prefix, e)
                    self.errors += 1
                    return False
                obs_metrics.TIER_FETCHES.labels("push", "ok").inc()
                stub = {"index": frag.index, "frame": frag.frame,
                        "view": frag.view, "slice": frag.slice,
                        "prefix": prefix, "size": end,
                        "bodyLen": info.body_len,
                        "bodyCrc": int(info.body_crc),
                        "blocks": info.block_n}
                tmp = frag.path + ".blob.tmp"
                with open(tmp, "wb") as f:
                    f.write(json.dumps(stub).encode())
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(tmp, frag.path + ".blob")
                frag._close_storage()
                frag.storage = None
                frag.tier_state = BLOB
                frag._cold_pending = None
                for p in (frag.path, frag.cache_path):
                    try:
                        os.remove(p)
                    except FileNotFoundError:
                        pass
            self.ledger.set_tier(frag, BLOB, end)
            obs_metrics.TIER_DEMOTIONS.labels("blob").inc()
            self.blob_pushes += 1
            self._transition()
        finally:
            self.ledger.pin(frag, False)
        self._pace()
        return True

    def fetch_blob(self, frag) -> None:
        """Materialize a blob-tier fragment's data file back onto
        local disk. Called UNDER ``frag._mu`` from the fragment's
        read-path gate (the caller reopens storage afterwards). The
        reassembled bytes are verified against the manifest's block
        crcs AND the footer's whole-body digest before the
        ``os.replace`` — a wrong answer can never be admitted, only a
        ColdFetchError raised (which blocks the slice until a retry
        succeeds)."""
        t0 = time.perf_counter()
        prefix = blob_mod.fragment_prefix(frag.index, frag.frame,
                                          frag.view, frag.slice)
        try:
            if self.store is None:
                raise ColdFetchError(
                    f"tier: no blob store configured for {prefix}")
            if _fp.ACTIVE is not None:
                _fp.ACTIVE.hit("tier.fetch", host="fetch",
                               path=frag.path)
            buf = blob_mod.fetch_fragment(self.store, prefix)
            man = blob_mod.read_manifest(self.store, prefix)
            info = integrity_mod.parse_footer(
                buf, int(man["bodyLen"]))
            if info is None:
                raise integrity_mod.CorruptionError(
                    f"blob fragment {prefix}: fetched file has no"
                    f" footer")
            integrity_mod.verify_body(buf, info)
            tmp = frag.path + ".fetching"
            with open(tmp, "wb") as f:
                f.write(buf)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, frag.path)
        except (OSError, ValueError) as e:
            corrupt = isinstance(e, integrity_mod.CorruptionError)
            obs_metrics.TIER_FETCHES.labels(
                "fetch", "corrupt" if corrupt else "error").inc()
            self.fetch_failures += 1
            self._mark_blocked(frag, str(e))
            if isinstance(e, ColdFetchError):
                raise
            raise ColdFetchError(
                f"tier: cold fetch failed for {prefix}: {e}") from e
        try:
            os.remove(frag.path + ".blob")
        except OSError:
            pass
        obs_metrics.TIER_FETCHES.labels("fetch", "ok").inc()
        obs_metrics.TIER_FAULT_SECONDS.observe(
            time.perf_counter() - t0)
        self.blob_fetches += 1
        self._unblock(frag)
        self._transition()

    def note_fetched(self, frag, nbytes: int) -> None:
        """The fragment finished its post-fetch cold reopen."""
        self.ledger.track(frag, COLD, nbytes)

    def _retry_blocked(self) -> int:
        """Re-attempt blocked fetches (store back up, objects
        repaired). Success unblocks the slice — reads resume without
        operator action."""
        with self._mu:
            keys = list(self._blocked)
        n = 0
        for key in keys:
            if self._stop.is_set():
                break
            frag = self._frags.get(key)
            if frag is None:
                continue
            try:
                with frag._mu:
                    if getattr(frag, "tier_state", HOT) != BLOB:
                        self._unblock(frag)
                        continue
                    frag._tier_fetch_locked()
                n += 1
                self._pace()
            except (OSError, ValueError):
                continue
        return n

    # -- blocked-slice surface (the executor consult) -------------------------

    def _mark_blocked(self, frag, reason: str) -> None:
        key = self.ledger.key_of(frag)
        with self._mu:
            if key not in self._blocked:
                sk = (frag.index, frag.slice)
                self._blocked_slices[sk] = \
                    self._blocked_slices.get(sk, 0) + 1
            self._blocked[key] = {"index": frag.index,
                                  "frame": frag.frame,
                                  "view": frag.view,
                                  "slice": frag.slice,
                                  "reason": reason,
                                  "since": time.time()}
        self.logger.printf(
            "tier: BLOCKED %s/%s/%s/%d (cold fetch failed): %s",
            frag.index, frag.frame, frag.view, frag.slice, reason)

    def _unblock(self, frag) -> None:
        self._unblock_key(self.ledger.key_of(frag))

    def _unblock_key(self, key: tuple) -> None:
        with self._mu:
            if self._blocked.pop(key, None) is None:
                return
            sk = (key[0], key[3])
            n = self._blocked_slices.get(sk, 0) - 1
            if n <= 0:
                self._blocked_slices.pop(sk, None)
            else:
                self._blocked_slices[sk] = n

    def slice_blocked(self, index: str, slice: int) -> bool:
        """True when a blob-tier fragment of (index, slice) cannot be
        fetched — the read path must not serve the slice locally
        (same contract as QuarantineRegistry.slice_blocked)."""
        if not self._blocked_slices:  # lock-free empty fast path
            return False
        return (index, slice) in self._blocked_slices

    # -- read-path hooks (called under frag._mu; ledger is a leaf) ------------

    def on_access(self, frag) -> None:
        """Every gated read lands here: stamp the ledger and feed the
        touch counter the prefetcher ranks by."""
        from ..sched import context as sched_context
        ctx = sched_context.current()
        tenant = getattr(ctx, "tenant", "") if ctx is not None else ""
        self.ledger.touch(frag, tenant)
        obs_metrics.TIER_TOUCH.labels(tenant or "default", frag.index,
                                      str(frag.slice)).inc()

    def note_fault(self, frag, nbytes: int) -> None:
        self.ledger.note_fault(frag, nbytes)

    def note_promoted(self, frag, nbytes: int, trigger: str) -> None:
        # The TIER_PROMOTIONS counter is incremented by the fragment's
        # _tier_promote_locked (the one site every trigger funnels
        # through) — only the ledger/lifetime accounting lives here.
        self.ledger.set_tier(frag, HOT, nbytes)
        self.promotions += 1
        self._transition()

    # -- prefetch -------------------------------------------------------------

    def prefetch_once(self) -> int:
        """Promote the hottest cold/blob fragments (by recent touch
        rate from the metric history) while resident stays under the
        low watermark. Returns promotions made."""
        cold_keys = self.ledger.keys(COLD) + self.ledger.keys(BLOB)
        if not cold_keys or self.history is None:
            return 0
        if self.busy_fn is not None and self.busy_fn():
            obs_metrics.TIER_PREFETCH.labels("skipped_busy").inc()
            return 0
        rates = self._touch_rates()
        if not rates:
            return 0
        budget = self.resident_budget
        low = int(self.low_watermark * budget) if budget > 0 else 0
        scored = sorted(
            cold_keys,
            key=lambda k: -rates.get((k[0], k[3]), 0.0))
        n = 0
        for key in scored:
            if self._stop.is_set():
                break
            if rates.get((key[0], key[3]), 0.0) <= 0.0:
                break
            frag = self._frags.get(key)
            if frag is None:
                continue
            e = self.ledger.get(frag)
            if e is None or e.pinned:
                continue
            if (budget > 0 and self.ledger.resident_bytes() + e.nbytes
                    > low):
                obs_metrics.TIER_PREFETCH.labels(
                    "skipped_budget").inc()
                break
            try:
                frag.promote(trigger="prefetch")
                obs_metrics.TIER_PREFETCH.labels("promoted").inc()
                n += 1
            except (OSError, ValueError) as e:
                obs_metrics.TIER_PREFETCH.labels("error").inc()
                self.logger.printf("tier: prefetch failed %s: %s",
                                   frag.path, e)
            self._pace()
        return n

    def _touch_rates(self) -> dict[tuple, float]:
        """(index, slice) -> mean touch rate over the recent history
        window. Touch counters are per-(tenant, index, slice); tenants
        sum — prefetch ranks fragments, not tenants."""
        out: dict[tuple, float] = {}
        try:
            res = self.history.series(family=TOUCH_FAMILY,
                                      window_s=600.0)
        except Exception:  # noqa: BLE001 - history is advisory
            return out
        for s in res.get("series", ()):
            labels = s.get("labels") or {}
            idx = labels.get("index")
            try:
                slc = int(labels.get("slice", ""))
            except (TypeError, ValueError):
                continue
            pts = [v for _t, v in s.get("points", ())]
            if not pts or idx is None:
                continue
            rate = sum(pts) / len(pts)
            out[(idx, slc)] = out.get((idx, slc), 0.0) + rate
        return out

    # -- bookkeeping ----------------------------------------------------------

    def _transition(self) -> None:
        self._last_transition = time.monotonic()

    def _pace(self) -> None:
        if self.pace_s:
            self._stop.wait(self.pace_s)

    def stall_age(self) -> Optional[float]:
        """Seconds since the last completed transition while work is
        pending, or None when nothing is waiting on the manager (the
        watchdog tier_stall input)."""
        if not self._work_pending:
            return None
        return time.monotonic() - self._last_transition

    def scrub_blob(self, frag) -> dict:
        """The scrubber's blob-tier leg: verify the fragment's blob
        objects against manifest crcs + body digest (same verdict
        shape as scrub_file). A corrupt verdict does NOT quarantine —
        the local node holds no bytes to distrust; it blocks the
        fetch path instead so the failure surfaces as degraded, not
        wrong."""
        if self.store is None:
            return {"corrupt": False, "coverage": "none",
                    "error": "no blob store", "blocks": 0}
        prefix = blob_mod.fragment_prefix(frag.index, frag.frame,
                                          frag.view, frag.slice)
        verdict = blob_mod.verify_fragment(self.store, prefix)
        if verdict.get("corrupt"):
            self._mark_blocked(
                frag, f"scrub: {verdict.get('error', 'corrupt')}")
        return verdict

    # -- exposition -----------------------------------------------------------

    def state(self) -> dict:
        counts = self.ledger.counts()
        with self._mu:
            blocked = [dict(v) for v in self._blocked.values()]
        return {
            "enabled": True,
            "residentBudget": self.resident_budget,
            "residentBytes": self.ledger.resident_bytes(),
            "highWatermark": self.high_watermark,
            "lowWatermark": self.low_watermark,
            "idleS": self.idle_s,
            "blobIdleS": self.blob_idle_s,
            "intervalS": self.interval_s,
            "prefetchIntervalS": self.prefetch_interval_s,
            "tiers": {t: {"fragments": n, "bytes": b}
                      for t, (n, b) in counts.items()},
            "tenantResident": self.ledger.tenant_resident(),
            "demotions": self.demotions,
            "rechills": self.rechills,
            "promotions": self.promotions,
            "blobPushes": self.blob_pushes,
            "blobFetches": self.blob_fetches,
            "fetchFailures": self.fetch_failures,
            "errors": self.errors,
            "blocked": blocked,
            "store": self.store.state() if self.store else None,
            "stallAgeS": (round(self.stall_age(), 1)
                          if self.stall_age() is not None else None),
        }

    def entries(self, tier: str = "") -> list[dict]:
        return self.ledger.entries(tier)
