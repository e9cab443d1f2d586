"""Fused Pallas TPU kernels for the count hot path.

XLA already fuses ``popcount(a & b)`` with its row reduction; the Pallas
variant exists to (a) control tiling explicitly for the long-row case (a 1 B
column row is 32 M words — 128 MB — streamed HBM→VMEM in double-buffered
tiles), and (b) guarantee a single pass with no intermediate even across
fusion-boundary surprises. On non-TPU backends everything falls back to the
XLA kernels (pilosa_tpu.ops.kernels), which are the semantics reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .kernels import _BITWISE

# Row/word tile sizes. 8×4096 u32 ×2 operands = 256 KB VMEM per step —
# small enough to double-buffer, wide enough to stream HBM at full rate.
_TILE_R = 8
_TILE_W = 4096
_LANES = 128


def platform_of(a: jax.Array) -> str:
    """Platform of the array's device (default backend for tracers and
    abstract values) — the input to pallas_mode."""
    try:
        return a.devices().pop().platform if hasattr(a, "devices") \
            else jax.default_backend()
    except Exception:  # noqa: BLE001 - tracer/abstract values
        return jax.default_backend()


def pallas_mode(platform: str) -> str | None:
    """How the serving path should run these Pallas kernels on
    ``platform``.

    DEFAULT IS XLA (returns None): the round-4 kernel-level A/B at the
    literal BASELINE shapes (benchmarks/PALLAS_AB.json) measured XLA
    fusion equal-or-faster on 5 of 6 serving shapes — 1.23x at the
    1 B-bit metric-of-record shape, 3.7x on a single long row, ~1.5x on
    TopN candidate blocks; the single Pallas "win" was 0.96x (noise).
    These kernels remain available as an explicit experiment
    (PILOSA_TPU_PALLAS=1|force → compiled on TPU) and as a correctness
    harness (=interpret, used by CPU tests), matching the reference's
    rule of dispatching to its asm path only when CPUID proves it pays
    (roaring/assembly_asm.go:15,40-80). The sparse-upload densify
    kernel (densify_pallas) is NOT gated here — scatter is XLA's known
    TPU weak spot, so the sparse-upload path selects it independently
    (see parallel.residency's sparse block builds).
    """
    import os
    v = os.environ.get("PILOSA_TPU_PALLAS", "xla")
    if v in ("1", "force", "auto"):
        # "auto" kept for round-3 compatibility: it now means "let the
        # recorded A/B decide", and the A/B said XLA — but an explicit
        # opt-in should still get the Pallas path on real TPU.
        return "compiled" if platform == "tpu" and v != "auto" else None
    if v == "interpret":
        return "interpret"
    return None


def _count_kernel(op_name, a_ref, b_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    words = _BITWISE[op_name](a_ref[:], b_ref[:])
    pc = jax.lax.population_count(words).astype(jnp.int32)
    tr, tw = pc.shape
    out_ref[:] += pc.reshape(tr, tw // _LANES, _LANES).sum(axis=1)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _op_count_padded(op: str, a: jax.Array, b: jax.Array,
                     interpret: bool = False) -> jax.Array:
    rows, words = a.shape
    grid = (rows // _TILE_R, words // _TILE_W)
    partials = pl.pallas_call(
        functools.partial(_count_kernel, op),
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((_TILE_R, _TILE_W), lambda i, j: (i, j)),
            pl.BlockSpec((_TILE_R, _TILE_W), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((_TILE_R, _LANES), lambda i, j: (i, 0)),
        interpret=interpret,
    )(a, b)
    return jnp.sum(partials, axis=-1)


def _eval_expr_ref(expr, leaves_ref):
    """Evaluate a hashable expr tree over a Pallas leaves ref: ``("leaf",
    i)`` loads leaf block i, ``(op, a, b)`` combines in VMEM — the whole
    PQL bitmap expression runs per tile with no HBM intermediates."""
    if expr[0] == "leaf":
        return leaves_ref[expr[1]]
    return _BITWISE[expr[0]](_eval_expr_ref(expr[1], leaves_ref),
                             _eval_expr_ref(expr[2], leaves_ref))


def _expr_count_kernel(expr, leaves_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    words = _eval_expr_ref(expr, leaves_ref)
    pc = jax.lax.population_count(words).astype(jnp.int32)
    tr, tw = pc.shape
    out_ref[:] += pc.reshape(tr, tw // _LANES, _LANES).sum(axis=1)


@functools.partial(jax.jit, static_argnums=(0, 2))
def expr_count_rows_pallas(expr, leaves: jax.Array,
                           interpret: bool = False) -> jax.Array:
    """Per-slice-row counts of a bitmap expression, one fused kernel.

    ``leaves`` is ``[n_leaves, S, W]`` u32; returns ``[S]`` int32 of
    ``sum(popcount(expr(leaves[:, s])))``. The expression tree, the
    popcount, and the word reduction all run tile-resident in VMEM —
    the serving-path generalization of the 2-operand count kernel
    (replacing roaring.go:1192-1268's per-container-pair loops for an
    arbitrary expression). Pads rows/words to tile multiples (zero
    words count zero).
    """
    n_leaves, rows, words = leaves.shape
    tile_w = min(_TILE_W, -(-words // _LANES) * _LANES)
    pr = (-rows) % _TILE_R
    pw = (-words) % tile_w
    if pr or pw:
        leaves = jnp.pad(leaves, ((0, 0), (0, pr), (0, pw)))
    grid = (leaves.shape[1] // _TILE_R, leaves.shape[2] // tile_w)
    partials = pl.pallas_call(
        functools.partial(_expr_count_kernel, expr),
        out_shape=jax.ShapeDtypeStruct((leaves.shape[1], _LANES),
                                       jnp.int32),
        grid=grid,
        in_specs=[pl.BlockSpec((n_leaves, _TILE_R, tile_w),
                               lambda i, j: (0, i, j))],
        out_specs=pl.BlockSpec((_TILE_R, _LANES), lambda i, j: (i, 0)),
        interpret=interpret,
    )(leaves)
    return jnp.sum(partials, axis=-1)[:rows]


def _eval_expr_ref_t(expr, leaves_ref):
    """_eval_expr_ref for the slice-major leaves layout of the TopN
    kernel: the block is ``[1, n_leaves, tile_w]``, so leaf i loads as
    ``leaves_ref[:, i, :]`` → ``[1, tile_w]``."""
    if expr[0] == "leaf":
        return leaves_ref[:, expr[1], :]
    return _BITWISE[expr[0]](_eval_expr_ref_t(expr[1], leaves_ref),
                             _eval_expr_ref_t(expr[2], leaves_ref))


def _topn_block_kernel(expr, rows_ref, leaves_ref, out_ref):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    words = rows_ref[0]                      # [TILE_R, tile_w]
    if expr is not None:
        src = _eval_expr_ref_t(expr, leaves_ref)  # [1, tile_w]
        words = jnp.bitwise_and(words, src)       # broadcast over rows
    pc = jax.lax.population_count(words).astype(jnp.int32)
    tr, tw = pc.shape
    out_ref[0] += pc.reshape(tr, tw // _LANES, _LANES).sum(axis=1)


@functools.partial(jax.jit, static_argnums=(0, 3))
def topn_block_count_pallas(expr, rows: jax.Array, leaves: jax.Array,
                            interpret: bool = False) -> jax.Array:
    """Per-(slice, candidate) counts of ``popcount(row ∩ expr)``.

    ``rows`` is ``[S, R, W]``, ``leaves`` ``[n_leaves, S, W]`` (ignored
    when ``expr`` is None → plain row popcounts). Returns ``[S, R]``
    int32. The TopN exact-count hot loop as one fused kernel: candidate
    tile, source-expression tile, AND, popcount, and reduction all stay
    in VMEM (the vectorized device replacement for the reference's
    sequential per-row IntersectionCount, fragment.go:560-614).
    """
    n_slices, rows_n, words = rows.shape
    tile_w = min(_TILE_W, -(-words // _LANES) * _LANES)
    pr = (-rows_n) % _TILE_R
    pw = (-words) % tile_w
    if pr or pw:
        rows = jnp.pad(rows, ((0, 0), (0, pr), (0, pw)))
        leaves = jnp.pad(leaves, ((0, 0), (0, 0), (0, pw)))
    grid = (n_slices, rows.shape[1] // _TILE_R, rows.shape[2] // tile_w)
    n_leaves = max(leaves.shape[0], 1)
    if leaves.shape[0] == 0:  # expr None: feed a 1-leaf dummy block
        leaves = jnp.zeros((1, n_slices, rows.shape[2]), jnp.uint32)
    # Slice-major leaves layout: the per-slice leaf block's trailing two
    # dims become (n_leaves, tile_w), satisfying the TPU tiling rule
    # (second-to-last must divide 8 OR equal the array dim — a size-1
    # slice block over [L, S, W] does neither when S isn't tiny).
    leaves_t = jnp.transpose(leaves, (1, 0, 2))  # [S, L, W]
    partials = pl.pallas_call(
        functools.partial(_topn_block_kernel, expr),
        out_shape=jax.ShapeDtypeStruct(
            (n_slices, rows.shape[1], _LANES), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, _TILE_R, tile_w), lambda s, i, j: (s, i, j)),
            pl.BlockSpec((1, n_leaves, tile_w), lambda s, i, j: (s, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, _TILE_R, _LANES),
                               lambda s, i, j: (s, i, 0)),
        interpret=interpret,
    )(rows, leaves_t)
    return jnp.sum(partials, axis=-1)[:, :rows_n]


def op_count_rows_pallas(op: str, a: jax.Array, b: jax.Array,
                         interpret: bool = False) -> jax.Array:
    """Fused ``sum(popcount(a ⊕ b), axis=-1)`` as one Pallas kernel.

    Accepts ``[n_words]`` or ``[n_rows, n_words]``; pads to tile multiples
    (zero words contribute zero to every count, so padding is free).
    """
    squeeze = a.ndim == 1
    if squeeze:
        a, b = a[None, :], b[None, :]
    if a.shape[0] == 1 and a.shape[1] % (_TILE_R * _LANES) == 0:
        # A single long row would be padded to _TILE_R rows (8× wasted
        # reads). Counts are position-invariant, so fold it into a row
        # block and sum the per-row partials.
        w = a.shape[1]
        folded = op_count_rows_pallas(
            op, a.reshape(_TILE_R, w // _TILE_R),
            b.reshape(_TILE_R, w // _TILE_R), interpret)
        total = jnp.sum(folded)
        return total if squeeze else total[None]
    rows, words = a.shape
    pr = (-rows) % _TILE_R
    pw = (-words) % _TILE_W
    if pr or pw:
        a = jnp.pad(a, ((0, pr), (0, pw)))
        b = jnp.pad(b, ((0, pr), (0, pw)))
    out = _op_count_padded(op, a, b, interpret)
    out = out[:rows]
    return out[0] if squeeze else out


# -- sparse densify: the cold-path upload killer ---------------------------
#
# A dense upload ships 128 KB per slice row regardless of density, after
# a host-side pack of the same size. The sparse path ships set words
# bucketed by 128-lane group — ``[T, 256, G]`` (lane, value) slots,
# G = max set words in any row's 128-word group — and densifies ON
# DEVICE with this kernel: G fully-vectorized one-hot OR passes over the
# VMEM-resident output tile. No scatter, no dynamic indexing: XLA's
# scatter lowering made the sparse path a loss, and Mosaic forbids
# scalar/dynamic-lane VMEM access, so the layout is arranged host-side
# to make the kernel a pure vector computation
# (ops.packed.bucket_rows). This is the device
# analogue of the reference materializing a row in O(containers), not
# O(row width) (roaring.go:253-285).

_DENSIFY_TILE_R = 8  # TPU block sublane minimum: 8 rows per grid step
_DENSIFY_LANES = 128  # output tile: words viewed as [sublanes, 128 lanes]
_DENSIFY_TILE_S = 32  # 128-word groups per grid step (bounds the VMEM
                      # stack: each unrolled G pass holds one
                      # [8, 32, 128] u32 temp = 128 KB)


def _densify_kernel(lane_ref, val_ref, out_ref):
    lanes = jax.lax.broadcasted_iota(
        jnp.uint32, (1, 1, _DENSIFY_LANES), 2)
    acc = jnp.zeros(out_ref.shape, jnp.uint32)
    for g in range(lane_ref.shape[2]):  # static: one vector pass per slot
        lane_g = lane_ref[:, :, g][:, :, None]   # [8, tile_s, 1]
        val_g = val_ref[:, :, g][:, :, None]
        acc = acc | jnp.where(lanes == lane_g, val_g, jnp.uint32(0))
    out_ref[:] = acc


@functools.partial(jax.jit, static_argnums=(2, 3))
def densify_pallas(lane: jax.Array, val: jax.Array, n_words: int,
                   interpret: bool = False) -> jax.Array:
    """Bucketed sparse rows → dense u32 rows.

    ``lane``/``val`` are ``[T, n_words/128, G]``: slot g of group s of
    row t holds a word value and its lane (0-127) within the group;
    ``val == 0`` slots are padding (OR no-ops, any lane). Returns
    ``[T, n_words]``. Produced by ops.packed.bucket_rows."""
    t_rows, subs, g_slots = lane.shape
    if subs * _DENSIFY_LANES != n_words:
        raise ValueError("lane/val buckets do not match n_words")
    pr = (-t_rows) % _DENSIFY_TILE_R
    if pr:
        lane = jnp.pad(lane, ((0, pr), (0, 0), (0, 0)))
        val = jnp.pad(val, ((0, pr), (0, 0), (0, 0)))
    t_pad = t_rows + pr
    # Mosaic's stack model keeps every unrolled G pass's temp alive
    # concurrently (G x [8, tile_s, 128] u32), so the sublane tile
    # shrinks as G grows to stay inside the ~16 MB scoped-VMEM limit:
    # G * tile_s * 4 KB <= 8 MB. Beyond G=256 the data is dense enough
    # that callers must take the dense path (cost gate enforces this).
    if g_slots > 256:
        raise ValueError("densify_pallas: G > 256 — block too dense "
                         "for the sparse path; pack dense instead")
    tile_s = min(_DENSIFY_TILE_S, subs, max(8, 2048 // g_slots))
    while tile_s > 1 and subs % tile_s:
        tile_s //= 2
    if subs % tile_s or (tile_s < 8 and tile_s != subs):
        # grid = subs//tile_s must cover every group exactly, and the
        # block sublane dim must divide 8 or equal subs (Mosaic rule).
        raise ValueError(f"densify_pallas: no legal sublane tile for "
                         f"subs={subs}, G={g_slots}")
    out = pl.pallas_call(
        _densify_kernel,
        out_shape=jax.ShapeDtypeStruct(
            (t_pad, subs, _DENSIFY_LANES), jnp.uint32),
        grid=(t_pad // _DENSIFY_TILE_R, subs // tile_s),
        in_specs=[
            pl.BlockSpec((_DENSIFY_TILE_R, tile_s, g_slots),
                         lambda i, j: (i, j, 0)),
            pl.BlockSpec((_DENSIFY_TILE_R, tile_s, g_slots),
                         lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec(
            (_DENSIFY_TILE_R, tile_s, _DENSIFY_LANES),
            lambda i, j: (i, j, 0)),
        interpret=interpret,
    )(lane, val)
    return out.reshape(t_pad, n_words)[:t_rows]
