"""Measure the sparse-upload densify path against dense device_put.

A cold candidate block ships as dense words, 128 KB per slice row
whatever its density. The sparse path ships set words bucketed by
128-lane group ([T, 256, G] lane/value slots — ops.packed.bucket_rows)
and densifies on device with G vectorized one-hot OR passes
(ops.pallas_kernels.densify_pallas). This harness measures, at c5-scale
block shapes:

- dense leg:   pack host → device_put [T, 32768] u32      (status quo)
- sparse leg:  device_put lane/val [T, 256, G] + densify  (new path)

plus the kernel-only dispatch time and first-call compile cost, and
writes benchmarks/DENSIFY.json. Run on the real chip.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "DENSIFY.json")


def main() -> None:
    import jax

    from pilosa_tpu.ops import packed
    from pilosa_tpu.ops.pallas_kernels import densify_pallas

    platform = jax.devices()[0].platform
    rng = np.random.default_rng(5)
    W = packed.WORDS_PER_SLICE  # 32768
    subs = W // 128

    out = {"platform": platform, "cases": []}
    # (tiles, set bits per row): c5-ish 256 slices x 64 candidates at
    # ~2000 and ~30 bits/row, and a denser 16K-bit variant for the
    # crossover. Every row reuses one synthetic sparse pattern.
    for t_rows, bits_per_row in ((256 * 64, 2000), (256 * 64, 30),
                                 (2048, 16000)):
        pos = np.sort(
            rng.choice(W * 32, size=bits_per_row, replace=False))
        widx = (pos >> 5).astype(np.int64)
        bitv = (np.uint32(1) << (pos & 31).astype(np.uint32))
        starts = np.concatenate(([0], np.flatnonzero(np.diff(widx)) + 1))
        uidx = widx[starts]
        uval = np.bitwise_or.reduceat(bitv, starts)
        # bucket one row, then broadcast to T rows
        groups = uidx >> 7
        counts = np.bincount(groups, minlength=subs)
        g_pad = 1 << (max(1, int(counts.max())) - 1).bit_length()
        st = np.zeros(subs + 1, np.int64)
        np.cumsum(counts, out=st[1:])
        rank = np.arange(len(uidx)) - st[groups]
        lane1 = np.zeros((subs, g_pad), np.uint32)
        val1 = np.zeros((subs, g_pad), np.uint32)
        lane1[groups, rank] = (uidx & 127).astype(np.uint32)
        val1[groups, rank] = uval
        lanes = np.broadcast_to(lane1, (t_rows, subs, g_pad)).copy()
        vals = np.broadcast_to(val1, (t_rows, subs, g_pad)).copy()

        dense = np.zeros((t_rows, W), np.uint32)
        dense[:, uidx] = uval

        jax.device_put(dense[:64]).block_until_ready()  # warm path
        t0 = time.perf_counter()
        d = jax.device_put(dense)
        d.block_until_ready()
        dense_s = time.perf_counter() - t0
        del d

        t0 = time.perf_counter()
        dl, dv = jax.device_put(lanes), jax.device_put(vals)
        jax.block_until_ready((dl, dv))
        upload_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        got = densify_pallas(dl, dv, W)
        got.block_until_ready()
        first_kernel_s = time.perf_counter() - t0  # includes compile
        ok = bool((np.asarray(got[:2]) == dense[:2]).all())
        t0 = time.perf_counter()
        for _ in range(8):
            got = densify_pallas(dl, dv, W)
        got.block_until_ready()
        kernel_ms = (time.perf_counter() - t0) / 8 * 1e3
        del dl, dv, got

        case = {
            "tiles": t_rows, "bits_per_row": bits_per_row,
            "g_slots": int(g_pad),
            "dense_mb": round(dense.nbytes / 1e6, 1),
            "sparse_mb": round((lanes.nbytes + vals.nbytes) / 1e6, 1),
            "dense_put_s": round(dense_s, 3),
            "sparse_put_s": round(upload_s, 3),
            "densify_first_s": round(first_kernel_s, 3),
            "densify_ms": round(kernel_ms, 2),
            "sparse_total_s": round(upload_s + kernel_ms / 1e3, 3),
            "speedup": round(dense_s / (upload_s + kernel_ms / 1e3), 2),
            "verified": ok,
        }
        print(json.dumps(case), flush=True)
        out["cases"].append(case)

    with open(OUT, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"wrote": OUT}))


if __name__ == "__main__":
    main()
