#!/usr/bin/env python3
"""Chip probe: what chip_smoke.py cannot see from outside the server.

One process on the chip(s), each check compared with numpy:

- the one Pallas kernel compiles under the installed jax — ``densify_pallas``
  (the default sparse-upload leg on a TPU) at 256 slices for each bucketed
  group width and the candidate-block form — and so do the fixed-shape
  ``shard_map`` builders left in ``parallel/mesh.py``;
- a leaf slab uploaded by the executor's own path is one shard per device
  of the mesh, and ``memory_stats()["bytes_in_use"]`` grows on every device.

Run it through the chip tool from the repo root:

    chiprun --chips 1 -- python3 benchmarks/chip_probe.py
    chiprun --chips 4 -- python3 benchmarks/chip_probe.py

The full record goes to ``chiprun_out/probe_<n>chip.json``; the last line
of stdout is ``{"ok": ..., "failed": [...]}``. Exit 1 when a check failed
or the backend is not a TPU (off the chip Pallas only runs interpreted,
which proves nothing about the compiler).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from pilosa_tpu.ops import packed  # noqa: E402
from pilosa_tpu.parallel import mesh as mesh_mod  # noqa: E402

W = packed.WORDS_PER_SLICE
SLICES = 256


def popc(a) -> int:
    return int(np.bitwise_count(a).sum())


def require(ok, what="answer differs from numpy's") -> None:
    """A check that holds under ``python -O`` too."""
    if not ok:
        raise RuntimeError(str(what)[:400])


def densify_case(mesh, rng, g_target: int, block_rows: int | None = None):
    """Sparse rows with up to ``g_target`` set words per 128-word group,
    bucketed as the upload path does, densified on the device."""
    n_rows = SLICES if block_rows is None else SLICES * block_rows
    subs = W // 128
    want = np.zeros((n_rows, W), dtype=np.uint32)
    for row in want:
        groups = rng.choice(subs, size=int(rng.integers(1, 64)),
                            replace=False)
        idx = np.concatenate([
            g * 128 + rng.choice(128, size=int(rng.integers(
                1, g_target + 1)), replace=False)
            for g in groups])
        row[idx] = rng.integers(1, 1 << 32, size=len(idx), dtype=np.uint32)
    sparse, _, _ = packed.pack_slab(
        [packed.unpack_to_bitmap(row) for row in want])
    require(sparse is not None, "the gate refused a width it admits")
    lanes, vals = sparse
    if block_rows is not None:
        shape = (SLICES, block_rows) + lanes.shape[1:]
        lanes, vals = lanes.reshape(shape), vals.reshape(shape)
    t0 = time.perf_counter()
    got = mesh_mod.densify_sharded(mesh, lanes, vals)
    got.block_until_ready()
    t1 = time.perf_counter()
    mesh_mod.densify_sharded(mesh, lanes, vals).block_until_ready()
    t2 = time.perf_counter()
    require((np.asarray(got).reshape(want.shape) == want).all())
    return {"G": int(lanes.shape[-1]), "shape": list(lanes.shape),
            "firstS": round(t1 - t0, 3),
            "secondS": round(t2 - t1, 4)}


def shard_map_builders(mesh, rng) -> dict:
    """name -> check, for the shard_map builders mesh.py keeps beside
    densify (the query programs are parallel/programs.py's, which
    chip_smoke.py drives through the server)."""
    leaves = rng.integers(0, 1 << 32, size=(2, 64, W), dtype=np.uint32)
    rows = rng.integers(0, 1 << 32, size=(64, 10, W), dtype=np.uint32)
    la = [mesh_mod.shard_slices(mesh, leaves[i]) for i in range(2)]
    ra = mesh_mod.shard_slices(mesh, rows)
    want_topn = np.bitwise_count(
        rows & leaves[0][:, None, :]).sum(axis=(0, 2)).tolist()

    def same(got, want):
        require(got == want, (str(got)[:180], str(want)[:180]))

    def topn_counts():
        vals, _ = mesh_mod.topn_counts(mesh, "and", ra, la[0], 3)
        same(list(vals), sorted(want_topn, reverse=True)[:3])

    def query_step():
        n_i, n_u, _, _ = mesh_mod.query_step(mesh, la[0], la[1], ra, 3)
        same((n_i, n_u), (popc(leaves[0] & leaves[1]),
                          popc(leaves[0] | leaves[1])))

    return {
        "xla:count_op": lambda: same(
            mesh_mod.count_op(mesh, "and", la[0], la[1]),
            popc(leaves[0] & leaves[1])),
        "xla:topn_counts": topn_counts,
        "xla:query_step": query_step,
    }


def layout_and_hbm(n_dev: int) -> dict:
    """Two 256-slice leaf slabs through the executor's own upload path:
    where their shards are, and what each device's allocator says."""
    from pilosa_tpu import SLICE_WIDTH
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.models.holder import Holder
    from pilosa_tpu.parallel import programs, residency

    def in_use():
        return [(d.memory_stats() or {}).get("bytes_in_use", 0)
                for d in jax.devices()]

    before = in_use()
    rng = np.random.default_rng(5)
    with tempfile.TemporaryDirectory(prefix="chip_probe_") as tmp:
        holder = Holder(tmp)
        holder.open()
        frame = holder.create_index("i").create_frame("f")
        cols = [np.unique(rng.integers(0, SLICES * SLICE_WIDTH,
                                       size=3_000_000, dtype=np.uint64))
                for _ in range(2)]
        for row, c in enumerate(cols):
            frame.import_bits(np.full(len(c), row, np.uint64), c)
        ex = Executor(holder, host="h")
        ex._cost_model_enabled = False      # the device leg, not the router
        got = ex.execute("i", 'Count(Intersect(Bitmap(frame="f", rowID=0),'
                              ' Bitmap(frame="f", rowID=1)))')
        want = len(np.intersect1d(cols[0], cols[1]))
        require(got == [want], (got, want))
        require(ex.device_fallbacks == 0, "device fallback")
        cache = residency.device_cache()
        slabs = [{"shape": list(arr.shape),
                  "shards": [{"device": s.device.id,
                              "shape": list(s.data.shape)}
                             for s in arr.addressable_shards]}
                 for arr in list(cache._lru.values())]
        after = in_use()
        snapshot = cache.snapshot()
        ex.close()
        holder.close()
    growth = [a - b for a, b in zip(after, before)]
    require(len(slabs) == 2, slabs)
    for slab in slabs:
        require(sorted(s["device"] for s in slab["shards"]) == sorted(
            d.id for d in jax.devices()), slab)
        require({tuple(s["shape"]) for s in slab["shards"]} == {
            (slab["shape"][0] // n_dev, W)}, slab)
    require(all(g > 0 for g in growth), growth)
    return {"answer": got[0],
            "bucket": programs.slice_bucket(SLICES, n_dev),
            "slabs": slabs, "hbmBytesInUseBefore": before,
            "hbmBytesInUseAfter": after, "hbmGrowth": growth,
            "perDeviceBytes": snapshot["perDeviceBytes"]}


def main() -> int:
    dev = jax.devices()[0]
    out: dict = {"device": {"platform": dev.platform,
                            "kind": dev.device_kind,
                            "count": len(jax.devices())},
                 "jax": jax.__version__, "checks": {}}
    if dev.platform != "tpu":
        sys.stderr.write(f"chip_probe: backend is {dev.platform!r}, not"
                         " 'tpu': nothing here compiles for a chip\n")
        return 1
    mesh = mesh_mod.make_mesh()
    n_dev = mesh.shape[mesh_mod.AXIS_SLICES]
    out["mesh"] = dict(mesh.shape)
    rng = np.random.default_rng(0)

    checks: dict = {}
    for g in (1, 2, 4, 8, 16, 32):
        checks[f"densify_leaf_G{g}"] = (
            lambda g=g: densify_case(mesh, rng, g))
    checks["densify_block_R10_G8"] = lambda: densify_case(
        mesh, rng, 8, block_rows=10)
    checks.update(shard_map_builders(mesh, rng))
    checks["layout_and_hbm"] = lambda: layout_and_hbm(n_dev)

    failed = []
    for name, fn in checks.items():
        # Every check runs and is recorded, the refused ones by name: a
        # kernel the compiler refuses is a finding, and the exit code
        # says that there was one.
        t0 = time.perf_counter()
        try:
            rec = {"ok": True, "info": fn()}
        except Exception as e:  # noqa: BLE001 - recorded, and exit 1
            rec = {"ok": False,
                   "error": f"{type(e).__name__}: {e}"[:1500]}
            failed.append(name)
        rec["seconds"] = round(time.perf_counter() - t0, 3)
        out["checks"][name] = rec
        print(name, json.dumps(rec)[:400], flush=True)
    out["compileStats"] = mesh_mod.compile_stats()

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out",
                        f"probe_{len(jax.devices())}chip.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"ok": not failed, "failed": failed,
                      "device": out["device"]}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
