"""The one Pallas TPU kernel of the package: sparse densify.

Counts, TopN and every other query program are XLA fusions built by
``parallel.programs``: on the chip they stream at ~90 % of the HBM
roofline (PERF.md), so no hand kernel is kept for them. Densify is
different: it turns a sparse upload into a dense slab on the device,
which in XLA is a scatter - the TPU's weak spot. It is selected from
the input (the gate of ``ops.packed.pack_slab``) and from the platform
(``parallel.mesh.densify_mode``), and off a TPU it only runs with
``interpret=True``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

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
# (ops.packed.pack_slab). This is the device
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
    ``[T, n_words]``. Produced by ops.packed.pack_slab."""
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
