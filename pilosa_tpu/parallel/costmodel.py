"""Calibrated device/host routing for the executor's fast paths.

The reference has one path and always takes it (executor.go:1103-1236's
host map-reduce). This build has two — the roaring host path and the
mesh device path — and the right one depends on hardware the code can't
know statically: the host's roaring rate, the host→device link, the
device's own streaming rate and the fixed cost of one dispatch + fetch.
A fixed slice threshold therefore mis-routes on one machine or another.

So the executor calibrates at first mesh use — six probes, each a few
milliseconds beside a local chip, measured in THIS process on THIS
device and kept nowhere else — and predicts per query:

- ``sync_s``     — one no-op dispatch + result fetch round trip (the
                   device path's fixed cost);
- ``host_bps``   — the roaring intersection-count rate on this host
                   (the host path's per-byte cost on packed words);
- ``host_visit_s`` — what the host path pays for one leaf row of one
                   slice whatever the row holds (fragment look-up, row
                   extraction, one slice-pool task): the served host
                   path is a per-slice walk in Python, and at real
                   widths the walk, not the popcount, is its cost;
- ``upload_bps`` — host→device transfer rate of a packed block;
- ``pack_bps``   — host-side roaring→dense pack rate;
- ``device_bps`` — the fused popcount kernel's streaming rate on the
                   attached device.

Routing rule: the device serves unless the predicted host cost is a
CLEAR win (< margin × device cost, margin 0.5 by default). The margin
keeps marginal shapes on the device, where residency caching and
dispatch batching improve repeat queries; the env override
``PILOSA_TPU_COST_MARGIN`` tunes it, ``PILOSA_TPU_COST_MODEL=0``
disables the veto entirely (pre-calibration behavior).

What is priced is what EVERY query of a shape pays. A cold slab that
the residency cache keeps is filled once, by the leg that finds it
cold, and read many times, while the host path is paid on every read:
the executor passes ``cold_bytes`` to ``device_pays`` only for what is
packed again on every query (a streaming leg's block, a slab larger
than the whole residency budget) and 0 for slabs that stay. A veto
that depended on residency would keep itself true: the host answer
fills nothing, so the next read of the same rows finds them cold
again. The drift loop's samples follow the same line
(``Executor._timed_device_leg``): a leg that filled a slab is not a
sample of what ``device_scale`` prices.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass

import numpy as np


@dataclass
class Calibration:
    """Measured constants of one (host, device) pair. Every field is a
    measurement (``get_model``) or an explicit injection (tests); there
    are no defaults to fall back on."""
    sync_s: float       # one dispatch + fetch round trip, seconds
    host_bps: float     # roaring count throughput, bytes/second
    host_visit_s: float  # host path's fixed cost of one leaf of one slice
    upload_bps: float   # host→device transfer rate
    pack_bps: float     # host-side roaring→dense pack rate
    device_bps: float   # fused popcount streaming rate on the device
    # Drift-correction multipliers, adjusted by the feedback loop when
    # predicted and observed leg costs diverge (CostModel.record).
    host_scale: float = 1.0
    device_scale: float = 1.0
    # Extra multiplier for STREAMING device legs (block re-packed every
    # query): with packing priced by pack_bps these should predict
    # ~true, and their own scale lets the drift loop correct residual
    # streaming-only error without fighting the resident legs'
    # device_scale over one knob.
    stream_scale: float = 1.0

    def device_cost(self, total_bytes: int, cold_bytes: int = 0,
                    streaming: bool = False,
                    crossings: int = 1) -> float:
        # cold_bytes = data PACKED host-side (roaring → dense words at
        # pack_bps) and shipped at the measured transfer rate before
        # the kernel can stream it. A placement passes what the shape
        # packs on EVERY query (0 for slabs residency keeps: their one
        # fill is no read's price); a prediction of one leg's wall
        # passes what that leg packs.
        # crossings = host↔device round trips the plan actually pays:
        # a fused multi-op tree (executor._device_batch_run) dispatches
        # ONE program for the whole tree, so it pays sync_s once — not
        # once per Count/TopN call the tree contains.
        cost = (self.sync_s * crossings + cold_bytes / self.upload_bps
                + cold_bytes / self.pack_bps
                + total_bytes / self.device_bps) * self.device_scale
        if streaming:
            cost *= self.stream_scale
        return cost

    def host_cost(self, total_bytes: int, visits: int = 0) -> float:
        # visits = leaves × slices the host path walks, one fragment
        # look-up and one row extraction each: priced on the byte term
        # alone, a 2-leaf Count at 256 slices read 4.5 ms where the
        # served host path took ×170–256 that (chip run, PR 21), and
        # twelve callers paid for the drift loop's correction.
        return ((total_bytes / self.host_bps + visits * self.host_visit_s)
                * self.host_scale)

    def to_dict(self) -> dict:
        return {"sync_s": self.sync_s, "host_bps": self.host_bps,
                "host_visit_s": self.host_visit_s,
                "upload_bps": self.upload_bps,
                "pack_bps": self.pack_bps,
                "device_bps": self.device_bps,
                "host_scale": self.host_scale,
                "device_scale": self.device_scale,
                "stream_scale": self.stream_scale}


# Feedback-loop tuning: recalibrate a leg once it has DRIFT_MIN_SAMPLES
# observations whose median actual/predicted ratio leaves
# [1/DRIFT_BOUND, DRIFT_BOUND]; scales clamp to
# [1/_SCALE_CLAMP, _SCALE_CLAMP].
DRIFT_MIN_SAMPLES = 12
DRIFT_BOUND = 2.0
# Wide clamp: startup probes on shared VMs have been observed ~100x off
# (the exact scenario the loop exists to fix); the clamp only guards
# unbounded runaway, not plausible correction magnitudes.
_SCALE_CLAMP = 256.0


class CostModel:
    """Routing predictions + the closed feedback loop over them.

    One bad startup probe would otherwise mis-price every query until
    restart, so every routed query records (predicted, actual) for the
    leg it ran; when the median drift of a leg exceeds DRIFT_BOUND x,
    that leg's scale multiplier is folded by the observed median — the
    model re-converges in-process. The loop is the safety net, not the
    way onto the device: the probes price the first query where the
    hundredth is placed. Its samples are of what a scale prices, held
    against constants measured ALONE: a leg that filled a slab is no
    sample, and a leg among N running legs shares one interpreter and
    one device with them, so what it cost alone lies between its wall
    over N and its wall (Executor._timed_leg gives both ends). A scale
    grows only where even the lower ends are over the bound and shrinks
    only where even the upper ends are under it: load is not drift, and
    folding it into the scale of whichever leg was running sent the
    next read to the other leg, whose scale then grew in its turn.
    Nothing is kept across processes: a restart measures again."""

    def __init__(self, cal: Calibration, margin: float = 0.5):
        self.cal = cal
        self.margin = margin
        self.recalibrations = 0
        self._mu = threading.Lock()
        self._drift = {"host": deque(maxlen=64),
                       "device": deque(maxlen=64),
                       "device_stream": deque(maxlen=64)}

    _SCALE_ATTR = {"host": "host_scale", "device": "device_scale",
                   "device_stream": "stream_scale"}

    def device_pays(self, total_bytes: int, cold_bytes: int = 0,
                    streaming: bool = False,
                    host_bytes: int | None = None,
                    crossings: int = 1, host_visits: int = 0) -> bool:
        """False only when the host path is a clear predicted win.

        ``host_bytes`` prices the host alternative on ITS real byte
        walk when it differs from the device operand block — a fused
        multi-op tree deduplicates shared leaf slabs on device, while
        the per-call host path re-walks each call's leaves (and packs
        every TopN candidate row); pricing both sides on the
        deduplicated block systematically over-charged the mesh leg
        for exactly the multi-op queries fusion accelerates.
        ``crossings`` is the number of device dispatches the plan pays
        (1 for a fused tree, whatever the chunk loop needs otherwise).
        ``host_visits`` is the leaves × slices the host alternative
        walks (Calibration.host_cost). ``cold_bytes`` is what the shape
        packs and ships on EVERY query, not a kept slab's one fill.
        """
        host = self.cal.host_cost(
            host_bytes if host_bytes is not None else total_bytes,
            host_visits)
        device = self.cal.device_cost(total_bytes, cold_bytes,
                                      streaming, crossings=crossings)
        return host >= self.margin * device

    def predict(self, leg: str, total_bytes: int,
                cold_bytes: int = 0, host_visits: int = 0) -> float:
        if leg == "device":
            return self.cal.device_cost(total_bytes, cold_bytes)
        if leg == "device_stream":
            return self.cal.device_cost(total_bytes, cold_bytes,
                                        streaming=True)
        return self.cal.host_cost(total_bytes, host_visits)

    def record(self, leg: str, predicted_s: float, actual_s: float,
               wall_s: float | None = None) -> None:
        """Feed one routed query's (predicted, actual) leg cost back
        into the model; recalibrates when the median drift of that leg
        exceeds DRIFT_BOUND in either direction. ``actual_s`` is the
        least the leg can have cost alone and ``wall_s`` the most (its
        wall among other legs; the same where it ran alone): the scale
        is folded up by the median of the lower ends once THAT is over
        the bound, down by the median of the upper ends once THAT is
        under it."""
        if predicted_s <= 0 or actual_s <= 0:
            return
        if wall_s is None or wall_s < actual_s:
            wall_s = actual_s
        lo, hi = actual_s / predicted_s, wall_s / predicted_s
        with self._mu:
            d = self._drift.get(leg)
            if d is None:
                return
            d.append((lo, hi))
            if len(d) < DRIFT_MIN_SAMPLES:
                return
            if (len(d) > DRIFT_MIN_SAMPLES and lo <= DRIFT_BOUND
                    and hi >= 1.0 / DRIFT_BOUND):
                # The window was inside the bound a sample ago (it is
                # cleared whenever it is not) and a sample inside it
                # cannot take a median out: a served read pays an
                # append here, not two sorts under the lock.
                return
            med = sorted(lo for lo, _ in d)[len(d) // 2]
            if med <= DRIFT_BOUND:
                med = sorted(hi for _, hi in d)[len(d) // 2]
                if med >= 1.0 / DRIFT_BOUND:
                    return
            attr = self._SCALE_ATTR[leg]
            scale = getattr(self.cal, attr) * med
            scale = min(max(scale, 1.0 / _SCALE_CLAMP), _SCALE_CLAMP)
            setattr(self.cal, attr, scale)
            d.clear()
            self.recalibrations += 1

    def drift_snapshot(self) -> dict:
        with self._mu:
            out = {}
            for leg, d in self._drift.items():
                vals = sorted(lo for lo, _ in d)
                out[leg] = {
                    "n": len(vals),
                    "median": round(vals[len(vals) // 2], 3) if vals
                    else None}
            out["recalibrations"] = self.recalibrations
            out["hostScale"] = round(self.cal.host_scale, 4)
            out["deviceScale"] = round(self.cal.device_scale, 4)
            out["streamScale"] = round(self.cal.stream_scale, 4)
            return out


def _measure_sync_s(mesh) -> float:
    """One no-op dispatch + fetch on this mesh's first device. Compile
    excluded."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def probe(x):
        return x.sum()

    x = jax.device_put(jnp.ones(128, jnp.int32), mesh.devices.flat[0])
    int(probe(x))  # compile + warm
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        int(probe(x))  # int() forces the result fetch
        best = min(best, time.perf_counter() - t0)
    return max(best, 1e-6)


def _measure_upload_bps(mesh, sync_s: float) -> float:
    """Host→device transfer rate for a packed block. The measured wall
    time includes one round-trip floor (which device_cost prices
    separately as sync_s), so subtract it rather than count it twice."""
    import jax

    buf = np.zeros(4 << 20, dtype=np.uint32)  # 16 MB
    dev = mesh.devices.flat[0]
    jax.device_put(buf, dev).block_until_ready()  # warm the path
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        jax.device_put(buf, dev).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    transfer_s = max(best - sync_s, best / 10, 1e-9)
    return buf.nbytes / transfer_s


_DEVICE_PROBE_PASSES = 32


def _measure_device_bps(mesh, sync_s: float) -> float:
    """The fused AND+popcount+sum kernel's streaming rate over a block
    resident on this mesh's first device, times the number of devices
    a slab is sharded over (each streams its own share). One dispatch
    makes several dependent passes over the block, so that the kernel
    time stands clear of the round-trip floor subtracted from it."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def probe(a, b):
        def one_pass(i, acc):
            words = a & (b ^ i.astype(jnp.uint32))
            return acc + jnp.sum(
                jax.lax.population_count(words).astype(jnp.int32))
        return jax.lax.fori_loop(0, _DEVICE_PROBE_PASSES, one_pass,
                                 jnp.int32(0))

    dev = mesh.devices.flat[0]
    a = jax.device_put(np.full(4 << 20, 0x0F0F0F0F, np.uint32), dev)
    b = jax.device_put(np.full(4 << 20, 0x33333333, np.uint32), dev)
    int(probe(a, b))  # compile + warm
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        int(probe(a, b))
        best = min(best, time.perf_counter() - t0)
    kernel_s = max(best - sync_s, best / 10, 1e-9)
    return ((a.nbytes + b.nbytes) * _DEVICE_PROBE_PASSES / kernel_s
            * mesh.devices.size)


def _measure_pack_bps() -> float:
    """Host-side roaring→dense packing rate (what a cold slab and every
    streaming device leg pay before the upload)."""
    from ..ops import packed
    from ..storage import roaring

    rng = np.random.default_rng(3)
    storage = roaring.Bitmap.from_sorted(np.sort(rng.choice(
        1 << 23, size=1 << 18, replace=False)).astype(np.uint64))
    out = np.zeros(packed.WORDS_PER_SLICE, dtype=np.uint32)
    packed.pack_storage_row(storage, 0, out)  # warm
    best = float("inf")
    for _ in range(3):
        out[:] = 0
        t0 = time.perf_counter()
        for row in range(8):
            packed.pack_storage_row(storage, row % 8, out)
        best = min(best, time.perf_counter() - t0)
    return 8 * out.nbytes / max(best, 1e-9)


def _measure_host_bps() -> float:
    """The host path's real per-byte rate: roaring intersection_count
    over dense bitmap containers (the shape the device path competes
    with), including the per-container Python dispatch cost."""
    from ..storage import roaring

    n_bits = 1 << 23  # 8 Mbit → 128 bitmap containers → 1 MB operands
    a = roaring.Bitmap.from_sorted(
        np.arange(0, n_bits, 2, dtype=np.uint64))
    b = roaring.Bitmap.from_sorted(
        np.arange(0, n_bits, 3, dtype=np.uint64))
    a.intersection_count(b)  # warm
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        a.intersection_count(b)
        best = min(best, time.perf_counter() - t0)
    # Bytes "processed" = both operands' packed words.
    return (2 * n_bits / 8) / max(best, 1e-9)


_cache: dict[str, Calibration] = {}
_cache_mu = threading.Lock()


def get_model(mesh, host_visit_probe, margin: float = 0.5) -> CostModel:
    """Calibrate once per backend platform per process; the margin is
    per-caller (a cached calibration must not freeze the first caller's
    margin for everyone). Measurement happens OUTSIDE the lock —
    concurrent queries must not stall behind it; a losing racer just
    discards its duplicate measurement. ``host_visit_probe`` is the
    caller's probe of its own host fan-out, ``() -> host_visit_s`` (the
    executor's: Executor.calibrate), asked only where this process has
    no calibration yet.

    The calibration lives in this process only. A file would carry one
    process's drift corrections into the next — and a parent commit's
    into the change measured after it on the same machine."""
    platform = mesh.devices.flat[0].platform
    with _cache_mu:
        cal = _cache.get(platform)
    if cal is None:
        sync_s = _measure_sync_s(mesh)
        cal = Calibration(
            sync_s=sync_s,
            host_bps=_measure_host_bps(),
            host_visit_s=host_visit_probe(),
            upload_bps=_measure_upload_bps(mesh, sync_s),
            pack_bps=_measure_pack_bps(),
            device_bps=_measure_device_bps(mesh, sync_s))
        with _cache_mu:
            cal = _cache.setdefault(platform, cal)
    return CostModel(cal, margin)
