"""Kernel-level Pallas-vs-XLA A/B at the BASELINE target shapes.

Round-3 verdict: the Pallas kernels are the TPU serving default, yet no
recorded measurement shows them beating XLA fusion anywhere — the
default was faith, not data. This harness settles it: each serving-path
kernel pair runs both legs at the literal benchmark shapes —

- ``op_count``           at bench.py's 1 B-bit chained-dispatch shape
                         (16 rows x 2^25 u32 words),
- ``expr_count_rows``    at the c4/c5 mesh Count shape (2-leaf
                         intersect over 256 slices) and the c3 shape
                         (10 slices),
- ``topn_block_count``   at the c3 exact-count shape (10 slices x 1000
                         candidates) and a c5-scale block (256 slices),

and persists both legs + the winner to ``benchmarks/PALLAS_AB.json``,
which bench.py stamps into the round artifact. The serving default
(ops.pallas_kernels.pallas_mode) is then chosen from this record — the
analogue of the reference dispatching to asm only when CPUID proves it
pays (roaring/assembly_asm.go:15,40-80).

Methodology (matches bench.py): the host↔device sync floor would
swamp per-call timing, so each measurement chains N asynchronous
dispatches and syncs once; reported ms is per dispatch. XLA legs run
before Pallas legs (device-queue contamination drains forward), and
both legs verify against numpy before timing.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

OUT_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "PALLAS_AB.json")


def _chain_ms(fn, n_iters: int, *args) -> float:
    """Per-dispatch ms over n_iters chained async dispatches, 1 sync."""
    import jax
    out = fn(*args)
    jax.block_until_ready(out)  # warmup/compile outside the window
    t0 = time.perf_counter()
    for _ in range(n_iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n_iters * 1e3


def _median(vals):
    return sorted(vals)[len(vals) // 2]


def _ab(name, xla_fn, pallas_fn, args, n_iters, trials=3, meta=None):
    import jax
    want = np.asarray(jax.block_until_ready(xla_fn(*args)))
    got = np.asarray(jax.block_until_ready(pallas_fn(*args)))
    assert (want == got).all(), f"{name}: leg mismatch"
    xla_ms = _median([_chain_ms(xla_fn, n_iters, *args)
                      for _ in range(trials)])
    pal_ms = _median([_chain_ms(pallas_fn, n_iters, *args)
                      for _ in range(trials)])
    row = {"kernel": name, "xla_ms": round(xla_ms, 3),
           "pallas_ms": round(pal_ms, 3),
           "pallas_over_xla": round(pal_ms / xla_ms, 3),
           "winner": "pallas" if pal_ms < xla_ms else "xla",
           "n_iters": n_iters, **(meta or {})}
    print(json.dumps(row), flush=True)
    return row


def main() -> None:
    import jax

    from pilosa_tpu.ops import pallas_kernels as pk
    from pilosa_tpu.ops.kernels import op_count_rows

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(json.dumps({"skipped": f"platform={platform}"}))
        return
    rng = np.random.default_rng(11)
    rows_out = []

    # --- op_count at the metric-of-record shape: 16 x 1 B-bit rows.
    n_words = 1 << 25
    a = jax.device_put(rng.integers(0, 2**32, (16, n_words), np.uint32))
    b = jax.device_put(rng.integers(0, 2**32, (16, n_words), np.uint32))
    rows_out.append(_ab(
        "op_count_1Gbit_rows",
        lambda x, y: op_count_rows("and", x, y),
        lambda x, y: pk.op_count_rows_pallas("and", x, y),
        (a, b), n_iters=64, meta={"shape": [16, n_words]}))
    # single long row (the fold-into-8 path) — 1 x 1 B bits
    a1, b1 = a[0], b[0]
    rows_out.append(_ab(
        "op_count_single_1Gbit_row",
        lambda x, y: op_count_rows("and", x, y),
        lambda x, y: pk.op_count_rows_pallas("and", x, y),
        (a1, b1), n_iters=64, meta={"shape": [1, n_words]}))
    del a, b, a1, b1

    # --- expr_count_rows: Count(Intersect(a,b)) per slice-row.
    expr = ("and", ("leaf", 0), ("leaf", 1))
    w = (1 << 20) // 32
    for n_slices, tag in ((256, "c5_256slices"), (10, "c3_10slices")):
        leaves = jax.device_put(
            rng.integers(0, 2**32, (2, n_slices, w), np.uint32))
        rows_out.append(_ab(
            f"expr_count_rows_{tag}",
            lambda lv: _xla_expr_count(expr, lv),
            lambda lv: pk.expr_count_rows_pallas(expr, lv),
            (leaves,), n_iters=128, meta={"shape": [2, n_slices, w]}))
        del leaves

    # --- topn_block_count: popcount(row & src) per (slice, candidate).
    for n_slices, n_cand, tag in ((10, 1000, "c3_10x1000"),
                                  (256, 64, "c5_256x64")):
        blk = jax.device_put(
            rng.integers(0, 2**32, (n_slices, n_cand, w), np.uint32))
        src = jax.device_put(
            rng.integers(0, 2**32, (1, n_slices, w), np.uint32))
        sexpr = ("leaf", 0)
        rows_out.append(_ab(
            f"topn_block_count_{tag}",
            lambda r, s: _xla_topn_block(sexpr, r, s),
            lambda r, s: pk.topn_block_count_pallas(sexpr, r, s),
            (blk, src), n_iters=32,
            meta={"shape": [n_slices, n_cand, w]}))
        del blk, src

    summary = {
        "platform": platform,
        "results": rows_out,
        "pallas_wins": sum(r["winner"] == "pallas" for r in rows_out),
        "total": len(rows_out),
    }
    with open(OUT_PATH, "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps({"wrote": OUT_PATH,
                      "pallas_wins": summary["pallas_wins"],
                      "total": summary["total"]}))


def _make_xla_legs():
    """Module-level jitted XLA legs (a fresh jit wrapper per call would
    recompile per dispatch and time the compiler, not the kernel —
    exactly the bug the first run of this harness had)."""
    import functools

    import jax
    import jax.numpy as jnp

    from pilosa_tpu.ops.kernels import _BITWISE

    def ev(node, lv):
        if node[0] == "leaf":
            return lv[node[1]]
        return _BITWISE[node[0]](ev(node[1], lv), ev(node[2], lv))

    @functools.partial(jax.jit, static_argnums=0)
    def expr_count(e, lv):
        pc = jax.lax.population_count(ev(e, lv)).astype(jnp.int32)
        return jnp.sum(pc, axis=-1)

    @functools.partial(jax.jit, static_argnums=0)
    def topn_block(e, r, lv):
        words = jnp.bitwise_and(r, ev(e, lv)[:, None, :])
        pc = jax.lax.population_count(words).astype(jnp.int32)
        return jnp.sum(pc, axis=-1)

    return expr_count, topn_block


_XLA_EXPR_COUNT = None
_XLA_TOPN_BLOCK = None


def _xla_expr_count(expr, leaves):
    global _XLA_EXPR_COUNT, _XLA_TOPN_BLOCK
    if _XLA_EXPR_COUNT is None:
        _XLA_EXPR_COUNT, _XLA_TOPN_BLOCK = _make_xla_legs()
    return _XLA_EXPR_COUNT(expr, leaves)


def _xla_topn_block(expr, rows, leaves):
    global _XLA_EXPR_COUNT, _XLA_TOPN_BLOCK
    if _XLA_TOPN_BLOCK is None:
        _XLA_EXPR_COUNT, _XLA_TOPN_BLOCK = _make_xla_legs()
    return _XLA_TOPN_BLOCK(expr, rows, leaves)


if __name__ == "__main__":
    main()
